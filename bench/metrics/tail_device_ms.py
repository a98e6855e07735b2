"""Device time of one execution of the stage-4 tail program (the
configuration's ``tail_program``), averaged over its executions in the
trace, ms."""


def read(rec):
    t = rec["trace"]
    if t is None or not t["program_calls"]:
        return None
    return t["program_device_s"] / t["program_calls"] * 1e3
