"""Share of its roofline the live overlay's split stage-4 tail reaches,
%: the mean least time of the dispatches whose spans start in the
device-traced window (``overlay.split_tail_work`` from the shapes the
program's ``stage:live_base_exact`` and ``stage:live_delta`` spans carry,
and the chip's peaks) over ``tail_device_ms.live``."""

import overlay


def read(rec):
    return overlay.tail_roofline(rec)
