"""Share of the traced window in which no operation ran on the device,
%: one minus the union of the device's operation intervals over the
window (``trace.py``)."""

import layers


def read(rec):
    return layers.idle_share(rec)
