"""95th percentile latency of every request due in the window, ms, as
``p95_ms`` would read it: recorded, not judged, since at 0.8× the knee
it swings with each seed's order of arrivals by more than any bound
admits."""

import stats


def read(rec):
    return stats.percentile_ms(rec["client"], 95)
