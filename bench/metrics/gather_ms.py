"""Host wall time of the mmap gathers (every ``host_gather:*`` stage of
the plan) per micro-batch over the traced part, ms (``PipelineStats``)."""

import layers


def read(rec):
    n = layers.batches(rec)
    names = [s for s in rec["stages"]["end"]["stages"]
             if s.startswith("host_gather:")]
    walls = [layers.stage_delta(rec, s) for s in names]
    walls = [w[0] for w in walls if w is not None]
    if not n or not walls:
        return None
    return sum(walls) / n * 1e3
