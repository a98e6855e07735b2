"""Wall time of the delta's exact scoring per micro-batch that has delta
candidates, over the traced part, ms: the program's ``stage:live_delta``
span (the gather of the delta rows and their split-tail dispatch), as
``PipelineStats`` records it."""

import layers


def read(rec):
    d = layers.stage_delta(rec, "live_delta")
    return None if d is None else d[0] / d[1] * 1e3
