"""The live cell's spans, counters and readers, on the CPU at a tiny
size: a traced run of ``tiny-live-mid`` (a live index whose compaction
threshold lies above the preload and the window's writes together, as
``msmarco-hybrid-live.ycsb-b``'s does) comes out correct with every
write acknowledged and no compaction in the window, records the overlay
path's spans and advances its counters, and its host readers read; the
device readers read a hand-written profile; and the split tail's
operations and bytes against counts by hand."""

import math

import numpy as np
import pytest
from jax.profiler import ProfileData

import harness
import layers
import overlay
import peaks
import run
import tiny

LIVE = "msmarco-hybrid-live.ycsb-b"
NAME = "tiny-live-mid"
HOST = ("overlay_ms.live", "stage1_ms.live", "delta_ms.live",
        "upsert_ms.live", "write_ms.live", "queue_wait_ms.live",
        "jax_compiles.live")
DEVICE = ("tail_device_ms.live", "tail_roofline.live", "device_idle.live",
          "idle_waiting.live")
RECORDS = ("live_overlay", "live_stage1", "live_base_exact", "live_delta",
           "live_upsert", "live_delete")
SPANS = ("stage:live_overlay", "stage:live_stage1", "stage:live_base_exact")


def _cell():
    """→ (cell, bench): ``tiny-live-mid`` under ``tiny-ycsb-mid``, with
    the live cell's metric lists extended to it."""
    bench = harness.benchmark()
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if LIVE in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [NAME]
    cfg_file = tiny.DATA / f"{NAME}.json"
    tr_file = tiny.DATA / "tiny-ycsb-mid.json"
    c = {"workload": {"name": NAME, "config": NAME,
                      "traffic": "tiny-ycsb-mid", "chips": 1},
         "config_file": cfg_file, "traffic_file": tr_file,
         "config": harness.load_json(cfg_file),
         "traffic": harness.load_json(tr_file)}
    return c, bench


@pytest.fixture(scope="module")
def traced():
    """A traced run → (its result, the serving process's record)."""
    recs = []
    serve = run.serve_window

    def kept(*a, **kw):
        recs.append(serve(*a, **kw))
        return recs[-1]
    run.serve_window = kept
    try:
        c, bench = _cell()
        r = run.run(NAME, tiny.SEED + 2, tiny.SECONDS, True,
                    require_tpu=False, c=c, bench=bench)
    finally:
        run.serve_window = serve
    return r, recs[0]


def test_mid_cycle_run_is_correct_and_compacts_nothing(traced):
    r, _ = traced
    assert r["correct"], r["checks"]
    w = r["info"]["writes"]
    assert r["failed"] == 0 and w["acked"] == w["sent"] > 0
    assert w["compactions_in_window"] == 0


def test_overlay_spans_are_recorded_and_the_counters_advance(traced):
    _, rec = traced
    start, close = rec["stages"]["start"], rec["stages"]["close"]
    for name in RECORDS:
        assert close["stages"][name]["dispatches"] > \
            start["stages"].get(name, {"dispatches": 0})["dispatches"], name
    a, b = start["counters"], close["counters"]
    for name in ("live_upserts", "live_deletes", "delta_rows_scored"):
        assert b[name] > a.get(name, 0), name
    # the window's updates: one upsert and one delete each
    assert b["live_upserts"] - a.get("live_upserts", 0) == \
        b["live_deletes"] - a.get("live_deletes", 0)
    assert b["delta_docs"] > a["delta_docs"] >= 16
    assert b["tombstones"] > a["tombstones"] >= 16
    assert b.get("live_compactions", 0) == 0
    raw = overlay.spans.load(rec)
    names = {s[1] for s in raw["spans"]}
    for name in SPANS:
        assert name in names, name
    shaped = [s[4] for s in raw["spans"] if s[1] in overlay.SHAPED]
    assert shaped and all(set(overlay.SHAPE_KEYS) <= set(s) for s in shaped)


def test_host_readers_read_and_nothing_compiles(traced):
    r, _ = traced
    m = r["metrics"]
    for name in HOST:
        assert name in m and math.isfinite(m[name]["value"]), name
    assert m["jax_compiles.live"]["value"] == 0
    # no TPU plane in a CPU trace: the device readers find nothing
    for name in DEVICE:
        assert name not in m, name


XSPACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1000000000 duration_ps: 400000000 }
    events { metadata_id: 2 offset_ps: 1500000000 duration_ps: 100000000 }
    events { metadata_id: 3 offset_ps: 2000000000 duration_ps: 200000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 0
    events { metadata_id: 4 offset_ps: 1000000000 duration_ps: 400000000 }
    events { metadata_id: 4 offset_ps: 2000000000 duration_ps: 200000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "jit_decompress_maxsim_scores_batch(11)" } }
  event_metadata { key: 2 value { id: 2 name: "jit_hybrid_scores(12)" } }
  event_metadata { key: 3 value { id: 3
    name: "jit_decompress_maxsim_scores_batch(13)" } }
  event_metadata { key: 4 value { id: 4 name: "%fusion = f32[8] fusion" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 1 name: "python" timestamp_ns: 0
    events { metadata_id: 1 offset_ps: 1100000000 duration_ps: 500000
      stats { metadata_id: 1 int64_value: 2 }
      stats { metadata_id: 2 int64_value: 200 }
      stats { metadata_id: 3 int64_value: 180 }
      stats { metadata_id: 4 int64_value: 32 }
      stats { metadata_id: 5 int64_value: 32 }
      stats { metadata_id: 6 int64_value: 128 }
      stats { metadata_id: 7 int64_value: 32768 }
      stats { metadata_id: 8 int64_value: 2 } }
    events { metadata_id: 2 offset_ps: 1900000000 duration_ps: 500000 }
  }
  event_metadata { key: 1 value { id: 1 name: "stage:live_base_exact" } }
  event_metadata { key: 2 value { id: 2 name: "stage:live_delta" } }
  stat_metadata { key: 1 value { id: 1 name: "B" } }
  stat_metadata { key: 2 value { id: 2 name: "C" } }
  stat_metadata { key: 3 value { id: 3 name: "Ld" } }
  stat_metadata { key: 4 value { id: 4 name: "pd" } }
  stat_metadata { key: 5 value { id: 5 name: "Lq" } }
  stat_metadata { key: 6 value { id: 6 name: "dim" } }
  stat_metadata { key: 7 value { id: 7 name: "K" } }
  stat_metadata { key: 8 value { id: 8 name: "nbits" } }
}
"""
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_split_tail_work_at_served_shapes():
    # two padded queries of 32 x 128 over 200 candidates of 180 slots,
    # 2-bit residuals (32 bytes a token), 2^15 centroids; (2, 200)
    # float32 scores out
    flops, nbytes = overlay.split_tail_work(B=2, C=200, Ld=180, pd=32,
                                            Lq=32, dim=128, K=32768,
                                            nbits=2)
    slots = 2 * 200 * 180                       # 72,000 token slots
    assert flops == 2 * slots * 32 * 128        # 589,824,000
    assert nbytes == (slots * 32                # packed residuals
                      + slots * 4               # centroid ids
                      + slots                   # token validity
                      + 2 * 200                 # candidate mask
                      + 2 * 32 * 128 * 4        # queries
                      + 2 * 32                  # query validity
                      + 32768 * 128 * 4         # centroid table
                      + 4 * 4                   # bucket weights
                      + 2 * 200 * 4)            # scores out
    assert nbytes == 2_304_000 + 288_000 + 72_000 + 400 + 32_768 + 64 \
        + 16_777_216 + 16 + 1_600
    t, bound = overlay.work.least_time(flops, nbytes, PEAK)
    assert bound == "memory" and t == nbytes / 819e9


def test_device_readers_on_a_hand_written_profile(tmp_path, monkeypatch):
    monkeypatch.setattr(harness, "WORK", tmp_path)
    rec = {"workload": {"name": NAME}, "device": {"kind": "TPU v5 lite"}}
    assert overlay.tail_device_s(rec) is None
    assert overlay.tail_roofline(rec) is None
    d = tmp_path / NAME / "trace"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(
        ProfileData.text_proto_to_serialized_xspace(XSPACE))
    # two executions of the split tail's program, 0.4 and 0.2 ms; the
    # fusion's program is another
    assert overlay.tail_device_s(rec) == pytest.approx([4e-4, 2e-4])
    assert layers.reader("tail_device_ms.live")(rec) == pytest.approx(0.3)
    # one shaped span in the device window (1.0-2.2 ms); the delta span
    # carries no shapes
    assert overlay.tail_shapes(rec) == [{
        "B": 2, "C": 200, "Ld": 180, "pd": 32, "Lq": 32, "dim": 128,
        "K": 32768, "nbits": 2}]
    _, nbytes = overlay.split_tail_work(**overlay.tail_shapes(rec)[0])
    peak = peaks.lookup("TPU v5 lite")
    share = layers.reader("tail_roofline.live")(rec)
    assert share == pytest.approx(
        100 * nbytes / peak["hbm_bytes_per_s"] / 3e-4)
    assert 0 < share <= 100


def test_stage_readers_per_batch_and_upserts_over_the_window():
    def snap(overlay_, upsert):
        return {"stages": {
            "live_overlay": {"wall_s": overlay_[0], "dispatches": overlay_[1],
                             "queries": 4 * overlay_[1]},
            "live_upsert": {"wall_s": upsert[0], "dispatches": upsert[1],
                            "queries": 0}}}
    rec = {"stages": {"start": {"stages": {}},
                      "begin": snap((0.5, 10), (0.02, 4)),
                      "end": snap((0.53, 13), (0.03, 6)),
                      "close": snap((0.8, 40), (0.12, 24))}}
    read = layers.reader
    # the traced part: 30 ms over 3 batches; the upserts over the window
    assert read("overlay_ms.live")(rec) == pytest.approx(10.0)
    assert read("upsert_ms.live")(rec) == pytest.approx(5.0)
    assert read("delta_ms.live")(rec) is None
    assert read("stage1_ms.live")(rec) is None
    # no upsert in the window
    rec["stages"]["start"] = rec["stages"]["close"]
    assert read("upsert_ms.live")(rec) is None


def test_write_ms_reads_window_writes_acknowledged():
    cl = {"w_due": np.array([-np.inf, -np.inf, 1.0, 1.0, 2.0, 2.0]),
          "w_ack": np.array([0.0, 0.0, 1.004, 1.010, 2.002, 2.5]),
          "w_status": np.array([0, 0, 0, 0, 0, 1], np.int8)}
    # 4, 10 and 2 ms; the preloaded writes and the failed one left out
    assert layers.reader("write_ms.live")({"client": cl}) == \
        pytest.approx(4.0)
    assert layers.reader("write_ms.live")({"client": {}}) is None
