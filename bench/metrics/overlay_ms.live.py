"""Wall time of the live overlay per micro-batch over the traced part,
ms: the program's ``stage:live_overlay`` span, the whole overlay search
of a batch (stage 1 over base and delta, the gathers, both split-tail
dispatches, the fusion and top-k), as ``PipelineStats`` records it."""

import layers


def read(rec):
    d = layers.stage_delta(rec, "live_overlay")
    return None if d is None else d[0] / d[1] * 1e3
