"""Share of its roofline the stage-4 tail reaches, %: the mean least
time of the tail calls dispatched in the traced part (``work.py``, from
their shapes and the chip's peaks) over the mean device time of one
execution of the tail program in the trace."""

import numpy as np

import peaks
import work


def read(rec):
    t, calls = rec["trace"], rec["tail_calls"]
    if t is None or not t["program_calls"] or not calls:
        return None
    peak = peaks.lookup(rec["device"]["kind"])
    least = np.mean([work.least_time(*work.tail_work(**c), peak)[0]
                     for c in calls])
    return 100.0 * least / (t["program_device_s"] / t["program_calls"])
