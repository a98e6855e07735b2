"""Mean wait of a request between the TCP front's submit and the start
of its micro-batch (the program's ``Result.t_start - t_arrival``), over
requests that arrived in the traced part, ms."""

import numpy as np


def read(rec):
    a, b = rec["interval"]
    r = rec["results"]
    r = r[(r[:, 0] >= a) & (r[:, 0] < b)]
    return float(np.mean(r[:, 1] - r[:, 0]) * 1e3) if len(r) else None
