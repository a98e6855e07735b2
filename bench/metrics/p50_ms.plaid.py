"""Median latency of every request due in the window, ms, as ``p50_ms``
reads it: recorded in the PLAID cell, not judged, since at 0.8× the knee
it moves with the host's speed by more than the largest bound admits
(PERF.md §2)."""

import stats


def read(rec):
    return stats.percentile_ms(rec["client"], 50)
