"""Programs JAX compiled in the serving process over the traced part of
the window: the delta of the program's ``jax_compiles`` counter
(``health()["counters"]``, read from ``PipelineStats``). 0 once warm-up
has compiled every shape the window dispatches."""


def read(rec):
    snaps = rec["stages"]
    if "begin" not in snaps or "end" not in snaps:
        return None
    begin, end = (snaps[x]["counters"].get("jax_compiles", 0)
                  for x in ("begin", "end"))
    return float(end - begin)
