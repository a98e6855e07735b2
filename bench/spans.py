"""The program's own host spans in a traced run, with what ``trace.read``
drops: the thread each span ran on and its stats (``qid``, ``qids``,
``h2d_bytes``), for the readers of quantities per request and per
micro-batch.

A span is (thread, name, start_s, end_s, stats), its thread being its
plane and line. The device window runs from the first to the last
operation on a device plane (``trace.DEVICE_PLANE``); the readers take
the spans that start inside it, as ``device_idle`` takes the device's
idle time there. A trace with no device plane (a CPU run) has no
window, and the readers find nothing.
"""

from __future__ import annotations

import functools
import pathlib

import harness
import trace


def read(pd) -> dict:
    """{"spans": [...], "window": (lo, hi) | None} of a trace (a
    ``jax.profiler.ProfileData``)."""
    spans, lo, hi = [], None, None
    for plane in pd.planes:
        if trace.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    a = e.start_ns * 1e-9
                    b = (e.start_ns + e.duration_ns) * 1e-9
                    lo = a if lo is None else min(lo, a)
                    hi = b if hi is None else max(hi, b)
        elif plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                spans += [((plane.name, i), e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9,
                           {k: v for k, v in e.stats})
                          for e in line.events if trace.SPAN.match(e.name)]
    return {"spans": spans, "window": None if lo is None else (lo, hi)}


@functools.lru_cache(maxsize=1)
def _read_file(path: str, mtime_ns: int) -> dict:
    from jax.profiler import ProfileData
    return read(ProfileData.from_file(path))


def load(rec: dict):
    """The spans of the newest trace of the run's cell; None where the
    profiler wrote none."""
    found = sorted((harness.WORK / rec["workload"]["name"] / "trace")
                   .glob("**/*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    path = pathlib.Path(found[-1])
    return _read_file(str(path), path.stat().st_mtime_ns)


def inside(raw: dict) -> list:
    """The spans that start inside the device window."""
    if raw is None or raw["window"] is None:
        return []
    lo, hi = raw["window"]
    return [s for s in raw["spans"] if lo <= s[2] <= hi]


def front_ms(raw: dict):
    """Mean self time of the TCP front per request, ms: each
    ``tcp:request`` span less the ``tcp:await`` spans inside it on the
    same thread. None where no request started in the window."""
    spans = inside(raw)
    awaits = [s for s in (raw["spans"] if raw else [])
              if s[1] == "tcp:await"]
    selfs = []
    for line, name, a, b, _ in spans:
        if name != "tcp:request":
            continue
        waited = sum(wb - wa for wl, _, wa, wb, _ in awaits
                     if wl == line and a <= wa and wb <= b)
        selfs.append(b - a - waited)
    return sum(selfs) / len(selfs) * 1e3 if selfs else None


def h2d_kb(raw: dict, first_stage: str):
    """Bytes the mmap gathers handed to the device per micro-batch, in
    10^3 bytes: the ``h2d_bytes`` of the ``stage:host_gather:*`` spans
    in the window over the ``stage:<first_stage>`` spans there that
    carry ``qids``. Spans without stats are left out: they are not the
    program's."""
    spans = inside(raw)
    sent = [s[4]["h2d_bytes"] for s in spans
            if s[1].startswith("stage:host_gather:")
            and "h2d_bytes" in s[4]]
    batches = sum(1 for s in spans
                  if s[1] == f"stage:{first_stage}" and "qids" in s[4])
    if not sent or not batches:
        return None
    return sum(sent) / batches / 1e3
