"""Wall time of the live stage 1 per micro-batch over the traced part,
ms: the program's ``stage:live_stage1`` span (SPLADE over the base CSR
with the tombstones excluded, the delta's own top-k, and the rebuild of
the delta's CSR when the delta has grown), as ``PipelineStats`` records
it."""

import layers


def read(rec):
    d = layers.stage_delta(rec, "live_stage1")
    return None if d is None else d[0] / d[1] * 1e3
