"""Reduce a profiler trace (``.xplane.pb``) to device busy time, the
device time of named programs, and idle gaps named by host spans.

Device planes are the ``/device:TPU:<n>`` planes. An interval in which
an operation runs is an event of the plane's ``XLA Ops`` line; busy
time is the union of those intervals, averaged over the devices that
ran anything. A program's executions are the events of the ``XLA
Modules`` line whose name holds the program's (jit) name. The traced
window runs from the first to the last event of any plane. An idle gap
is a stretch of the window in which no operation runs on a device; the
host spans (``TraceAnnotation`` events of the host plane whose names
carry a ``<layer>:`` prefix) that overlap it name it: each span name is
credited with the idle time it overlaps, and idle time that no span
overlaps goes to ``host:no_span``. Spans on different threads may
overlap, so the credits can sum to more than the idle time.
"""

from __future__ import annotations

import pathlib
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
SPAN = re.compile(r"^(stage|tcp|tail):")
MODULE = re.compile(r"\(\d+\)$")            # the program id suffix
LAYOUT = re.compile(r"\{[^}]*\}")
TOP = 10


def op_name(hlo: str) -> str:
    """A short name of an HLO op event: its result name, shape and kind,
    e.g. ``%fusion = f32[11796480] fusion``."""
    if " = " not in hlo:
        return hlo
    lhs, rhs = hlo.split(" = ", 1)
    words = LAYOUT.sub("", rhs).split(" ")
    kind = words[1].split("(")[0] if len(words) > 1 else ""
    return f"{lhs} = {words[0]} {kind}".strip()


def union(iv: np.ndarray) -> np.ndarray:
    """Union of (n, 2) [start, end) intervals → sorted, disjoint."""
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    ends = np.maximum.accumulate(iv[:, 1])
    new = np.ones(len(iv), bool)
    new[1:] = iv[1:, 0] > ends[:-1]
    starts = iv[new, 0]
    last = np.flatnonzero(new)
    stop = ends[np.r_[last[1:] - 1, len(iv) - 1]]
    return np.stack([starts, stop], axis=1)


def complement(busy: np.ndarray, lo: float, hi: float) -> np.ndarray:
    """[lo, hi) minus the disjoint sorted intervals ``busy``."""
    edges = np.clip(busy, lo, hi)
    starts = np.r_[lo, edges[:, 1]]
    ends = np.r_[edges[:, 0], hi]
    keep = ends > starts
    return np.stack([starts[keep], ends[keep]], axis=1)


def overlap(a: np.ndarray, b: np.ndarray) -> float:
    """Total length of the intersection of two disjoint sorted interval
    lists."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if hi > lo:
            total += hi - lo
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total


def _events(line):
    return [(e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
            for e in line.events]


def read(pd) -> dict:
    """The parts of a trace (a ``jax.profiler.ProfileData``) the
    reduction uses, as plain lists: ``devices`` {plane: {"ops": [...],
    "modules": [...]}} and ``spans`` [...], each event (name, start_s,
    end_s), and ``extent`` (first start, last end) over every plane."""
    devices, spans = {}, []
    lo, hi = np.inf, -np.inf
    for plane in pd.planes:
        for line in plane.lines:
            ev = _events(line)
            if ev:
                lo = min(lo, min(e[1] for e in ev))
                hi = max(hi, max(e[2] for e in ev))
            if DEVICE_PLANE.match(plane.name):
                d = devices.setdefault(plane.name, {"ops": [],
                                                    "modules": []})
                if line.name == "XLA Ops":
                    d["ops"] += ev
                elif line.name == "XLA Modules":
                    d["modules"] += ev
            elif plane.name.startswith("/host:"):
                spans += [e for e in ev if SPAN.match(e[0])]
    return {"devices": devices, "spans": spans, "extent": (lo, hi)}


def _iv(events):
    return np.array([(e[1], e[2]) for e in events]).reshape(-1, 2)


def reduce(raw: dict, program: str) -> dict:
    """→ {busy_s, window_s, program_calls, program_device_s, breakdown}.
    ``busy_s`` averages over the devices that ran an operation;
    ``program_*`` count the executions of ``program`` on every device."""
    lo, hi = raw["extent"]
    window = max(hi - lo, 0.0)
    busy_lists = {n: union(_iv(d["ops"])) for n, d in raw["devices"].items()
                  if d["ops"]}
    busy_s = (float(np.mean([np.sum(b[:, 1] - b[:, 0])
                             for b in busy_lists.values()]))
              if busy_lists else 0.0)
    ops, calls, prog_s = {}, 0, 0.0
    for d in raw["devices"].values():
        mods = sorted(d["modules"], key=lambda e: e[1])
        starts = np.array([e[1] for e in mods])
        for name, a, b in d["ops"]:
            i = np.searchsorted(starts, a, side="right") - 1
            mod = (MODULE.sub("", mods[i][0])
                   if i >= 0 and a < mods[i][2] else "(no program)")
            key = f"{mod}: {op_name(name)}"
            ops[key] = ops.get(key, 0.0) + (b - a)
        for name, a, b in d["modules"]:
            if program in name:
                calls += 1
                prog_s += b - a
    gaps = {}
    if busy_lists and window > 0:
        idle = [complement(b, lo, hi) for b in busy_lists.values()]
        by_name = {}
        for name, a, b in raw["spans"]:
            by_name.setdefault(name, []).append((name, a, b))
        all_spans = union(_iv(raw["spans"]))
        for g in idle:
            for name, ev in by_name.items():
                gaps[name] = gaps.get(name, 0.0) + overlap(g, union(_iv(ev)))
            free = np.sum(g[:, 1] - g[:, 0]) - overlap(g, all_spans)
            gaps["host:no_span"] = gaps.get("host:no_span", 0.0) + free
        gaps = {k: v / len(idle) for k, v in gaps.items()}
    top = sorted(ops.items(), key=lambda x: -x[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda x: -x[1])[:TOP]
    return {"busy_s": busy_s, "window_s": window, "program_calls": calls,
            "program_device_s": prog_s, "devices": len(busy_lists),
            "breakdown": {"device_ops": [[k, v] for k, v in top],
                          "idle_gaps": [[k, v] for k, v in top_gaps]}}


def reduce_dir(path, program: str):
    """Reduce the newest ``.xplane.pb`` under ``path``; None where the
    profiler wrote none."""
    from jax.profiler import ProfileData

    found = sorted(pathlib.Path(path).glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not found:
        return None
    return reduce(read(ProfileData.from_file(str(found[-1]))), program)
