"""The traced run's annotations, wrapped from the harness's side.

``instrument`` puts ``jax.profiler.TraceAnnotation`` spans around what
the serving process does on the host — each plan stage the executor
runs, the TCP front's JSON decoding and encoding, and the dispatch of
the stage-4 tail program — so that the trace reducer can name each
idle gap of the device by what the host was doing in it. It also
records the shapes of every tail call, from which ``work.py`` counts
the tail's operations and bytes. Nothing here changes what the program
computes; ``undo`` puts everything back.
"""

from __future__ import annotations

import json
import time
import types

from jax.profiler import TraceAnnotation

TRACE_SHARE = (0.3, 0.7)      # the traced part of the window
TRACE_MAX_S = 4.0


def window(seconds: float) -> tuple[float, float]:
    """Start and end of the traced part, in seconds from the window's
    start: the middle of the window, at most ``TRACE_MAX_S`` long."""
    a, b = (x * seconds for x in TRACE_SHARE)
    return a, min(b, a + TRACE_MAX_S)


def _json_shim():
    def loads(*a, **kw):
        with TraceAnnotation("tcp:json_loads"):
            return json.loads(*a, **kw)

    def dumps(*a, **kw):
        with TraceAnnotation("tcp:json_dumps"):
            return json.dumps(*a, **kw)
    return types.SimpleNamespace(loads=loads, dumps=dumps)


def instrument(cfg: dict):
    """→ (undo, tail_calls): ``tail_calls`` fills with one dict per
    dispatch of the configuration's tail program: its host time ``t``
    (``time.perf_counter``) and the shapes ``work.tail_work`` takes."""
    import repro.core.plaid as plaid
    import repro.serving.server as server
    from repro.serving.pipeline import StagePlan

    undo = []
    call_stage = StagePlan._call_stage

    def traced_stage(self, stage, cb):
        with TraceAnnotation(f"stage:{stage.name}"):
            return call_stage(self, stage, cb)
    StagePlan._call_stage = traced_stage
    undo.append(lambda: setattr(StagePlan, "_call_stage", call_stage))

    server_json = server.json
    server.json = _json_shim()
    undo.append(lambda: setattr(server, "json", server_json))

    name = cfg["tail_program"]
    tail = getattr(plaid, name)
    calls = []

    def traced_tail(q, packed, codes, valid, cand_mask, centroids, *a,
                    **kw):
        calls.append({"t": time.perf_counter(), "B": q.shape[0],
                      "Lq": q.shape[1], "dim": q.shape[2],
                      "C": packed.shape[1], "Ld": packed.shape[2],
                      "pd": packed.shape[3], "K": centroids.shape[0],
                      "k": kw["k"], "nbits": kw["nbits"]})
        with TraceAnnotation("tail:dispatch"):
            return tail(q, packed, codes, valid, cand_mask, centroids, *a,
                        **kw)
    setattr(plaid, name, traced_tail)
    undo.append(lambda: setattr(plaid, name, tail))

    def undo_all():
        for fn in reversed(undo):
            fn()
    return undo_all, calls
