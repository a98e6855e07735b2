"""Wall time of the SPLADE stage 1 (``splade_stage1``) per micro-batch
over the traced part, ms (``PipelineStats``)."""

import layers


def read(rec):
    d = layers.stage_delta(rec, "splade_stage1")
    return None if d is None else d[0] / d[1] * 1e3
