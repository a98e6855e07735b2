"""Bytes the mmap gathers hand to the device per micro-batch, in 10^3
bytes: the ``h2d_bytes`` of the program's ``stage:host_gather:*`` spans
in the device-traced window over its spans of the plan's first stage
there (``spans.py``)."""

import spans


def read(rec):
    return spans.h2d_kb(spans.load(rec), rec["first_stage"])
