"""Helpers the per-layer readers share: what happened inside the traced
part of the window, from the load generator's record, the program's
``Result`` stamps and its per-stage statistics."""

from __future__ import annotations

import importlib.util
import pathlib

import numpy as np

METRICS = pathlib.Path(__file__).resolve().parent / "metrics"


def reader(name: str):
    """The ``read`` function of ``metrics/<name>.py``. A metric that one
    cell reports under a name of its own, because there it moves another
    end-to-end metric, reads through the reader it copies."""
    spec = importlib.util.spec_from_file_location(f"metric_{name}",
                                                  METRICS / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def due_inside(rec: dict) -> np.ndarray:
    """Requests due in the traced part of the window."""
    a, b = rec["interval"]
    due = rec["client"]["due"]
    return (due >= a) & (due < b)


def stage_delta(rec: dict, name: str):
    """(wall_s, dispatches, queries) a plan stage added over the traced
    part; None where the stage did not run there."""
    snaps = rec["stages"]
    if "begin" not in snaps or "end" not in snaps:
        return None
    b, e = snaps["begin"]["stages"].get(name), snaps["end"]["stages"].get(name)
    if e is None:
        return None
    b = b or {"wall_s": 0.0, "dispatches": 0, "queries": 0}
    d = tuple(e[k] - b[k] for k in ("wall_s", "dispatches", "queries"))
    return d if d[1] > 0 else None


def batches(rec: dict):
    """Micro-batches the plan's first stage dispatched over the traced
    part."""
    d = stage_delta(rec, rec["first_stage"])
    return None if d is None else d[1]


def idle_share(rec: dict):
    """Share of the traced window, in %, in which no operation ran on
    the device."""
    t = rec["trace"]
    if t is None or t["busy_s"] <= 0 or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
