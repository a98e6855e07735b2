"""Device time of one execution of the live overlay's split stage-4 tail
(``decompress_maxsim_scores_batch``, the base and the delta dispatches
together), averaged over its executions in the trace, ms
(``overlay.py``)."""

import overlay


def read(rec):
    t = overlay.tail_device_s(rec)
    return None if t is None else sum(t) / len(t) * 1e3
