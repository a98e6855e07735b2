"""Share of the traced window, %, in which no operation ran on the device
while a request was inside the server: the reducer's idle credit of the
program's ``tcp:request`` spans (``trace.py``) over the window.
``device_idle.steady`` less this is idle with the server empty."""


def read(rec):
    t = rec["trace"]
    if t is None or t["window_s"] <= 0:
        return None
    gaps = dict(t["breakdown"]["idle_gaps"])
    if "tcp:request" not in gaps:
        return None
    return 100.0 * gaps["tcp:request"] / t["window_s"]
