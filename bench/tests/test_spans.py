"""The span readers (``spans.py``): the TCP front's self time and the
bytes per micro-batch, on hand-made spans, on a hand-written XSpace, and
on a trace recorded on one v5e chip with the server's own spans
(``data/v5e-hybrid-spans.textproto.gz``: 0.5 s of a traced
``msmarco-hybrid.steady`` run, the device's ``XLA Modules`` and ``XLA
Ops`` lines and the host's ``<layer>:`` spans with their stats, op names
shortened, stored as the text form of the XSpace proto)."""

import gzip
import math
import pathlib

import pytest
from jax.profiler import ProfileData

import layers
import spans
import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / \
    "v5e-hybrid-spans.textproto.gz"


def _raw(window=(1.0, 2.0)):
    return {"window": window, "spans": [
        # two requests in flight at once, each on its own handler thread
        (0, "tcp:request", 1.000, 1.010, {"qid": 1}),
        (0, "tcp:json_loads", 1.000, 1.001, {}),
        (0, "tcp:await", 1.002, 1.008, {"qid": 1}),
        (1, "tcp:request", 1.004, 1.020, {"qid": 2}),
        (1, "tcp:await", 1.005, 1.019, {"qid": 2}),
        # the plan's first stage, twice with the program's stats and
        # once without (the harness's own span of the same name)
        (2, "stage:splade_stage1", 1.003, 1.004, {"qids": "1 2"}),
        (2, "stage:splade_stage1", 1.003, 1.004, {}),
        (2, "stage:splade_stage1", 1.050, 1.051, {"qids": 3}),
        (2, "stage:host_gather:residuals", 1.004, 1.005,
         {"qids": "1 2", "h2d_bytes": 1000}),
        (2, "stage:host_gather:residuals", 1.004, 1.005, {}),
        (2, "stage:host_gather:residuals", 1.051, 1.052,
         {"qids": 3, "h2d_bytes": 3000}),
        (2, "stage:host_gather:residuals", 2.500, 2.501,
         {"qids": 4, "h2d_bytes": 5000}),
    ]}


def test_front_self_time_per_request_by_hand():
    # request 1: 10 ms less its 6 ms await; request 2: 16 less 14. The
    # other thread's await overlaps request 1 in time and is not its own
    assert spans.front_ms(_raw()) == pytest.approx(3.0)


def test_window_cut_and_no_window():
    # request 1 starts before the device window: only request 2 counts
    assert spans.front_ms(_raw((1.003, 2.0))) == pytest.approx(2.0)
    assert spans.front_ms(_raw(None)) is None
    assert spans.h2d_kb(_raw(None), "splade_stage1") is None
    assert spans.front_ms(None) is None


def test_bytes_per_batch_by_hand():
    # 1,000 + 3,000 bytes over two batches; the gather at 2.5 s lies past
    # the window, and spans without stats are the harness's
    assert spans.h2d_kb(_raw(), "splade_stage1") == pytest.approx(2.0)
    assert spans.h2d_kb(_raw(), "plaid_probe") is None


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 5000000 }
    events { metadata_id: 1 offset_ps: 1900000000 duration_ps: 100000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "%fusion = f32[8] fusion" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1100000000 duration_ps: 10000000000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 2 offset_ps: 1102000000 duration_ps: 6000000000
             stats { metadata_id: 1 int64_value: 7 } }
    events { metadata_id: 3 offset_ps: 1101000000 duration_ps: 500000 }
  }
  lines { id: 2 name: "python" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1103000000 duration_ps: 1000000
             stats { metadata_id: 2 str_value: "7 8" } }
    events { metadata_id: 5 offset_ps: 1104000000 duration_ps: 1000000
             stats { metadata_id: 2 str_value: "7 8" }
             stats { metadata_id: 3 int64_value: 1500 } }
  }
  event_metadata { key: 1 value { id: 1 name: "tcp:request" } }
  event_metadata { key: 2 value { id: 2 name: "tcp:await" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
  event_metadata { key: 4 value { id: 4 name: "stage:plaid_probe" } }
  event_metadata { key: 5 value { id: 5 name: "stage:host_gather:codes" } }
  stat_metadata { key: 1 value { id: 1 name: "qid" } }
  stat_metadata { key: 2 value { id: 2 name: "qids" } }
  stat_metadata { key: 3 value { id: 3 name: "h2d_bytes" } }
}
"""


def test_read_keeps_threads_stats_and_the_device_window():
    raw = spans.read(ProfileData.from_text_proto(XSPACE))
    assert raw["window"] == pytest.approx((0.001, 0.002))
    host = "/host:CPU"
    assert [(s[0], s[1]) for s in raw["spans"]] == [
        ((host, 0), "tcp:request"), ((host, 0), "tcp:await"),
        ((host, 1), "stage:plaid_probe"),
        ((host, 1), "stage:host_gather:codes")]
    assert raw["spans"][0][4] == {"qid": 7}
    assert raw["spans"][3][4] == {"qids": "7 8", "h2d_bytes": 1500}
    assert spans.front_ms(raw) == pytest.approx(4.0)
    assert spans.h2d_kb(raw, "plaid_probe") == pytest.approx(1.5)


def test_recorded_trace_reads_finite_and_waiting_within_idle():
    pd = ProfileData.from_text_proto(gzip.open(FIXTURE, "rt").read())
    rec = {"trace": trace.reduce(trace.read(pd), "fused_hybrid_tail")}
    waiting = layers.reader("idle_waiting.steady")(rec)
    idle = layers.reader("device_idle.steady")(rec)
    raw = spans.read(pd)
    front = spans.front_ms(raw)
    sent = spans.h2d_kb(raw, "splade_stage1")
    for v in (waiting, front, sent):
        assert v is not None and math.isfinite(v) and v > 0
    assert waiting <= idle
    # every program stage span names its requests; the harness's own
    # spans of the same names carry no stats
    stages = [s for s in raw["spans"] if s[1].startswith("stage:")]
    assert any("qids" in s[4] for s in stages)
    assert any(not s[4] for s in stages)
