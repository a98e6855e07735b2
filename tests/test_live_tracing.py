"""The live index's profiler spans and counters: the overlay path's
``stage:live_*`` spans (each recorded in ``PipelineStats`` as a plan
stage is), the writes' ``live:*`` spans, and the live counters and
gauges in every snapshot and in ``health()``; none of them on the
frozen path."""

import pathlib

import jax
import pytest
from jax.profiler import ProfileData

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.live import DELTA_WIDTH
from repro.index.splade_index import SpladeIndex, build_splade_index
from repro.serving.context import RequestContext
from repro.serving.engine import ServeEngine
from repro.serving.server import RetrievalServer

HOLD = 4                   # held-out passages, upserted by the tests
OVERLAY = ("stage:live_overlay", "stage:live_stage1",
           "stage:live_base_exact", "stage:live_delta")
WRITES = ("live:upsert", "live:delete", "live:compact")
SHAPES = ("B", "C", "Ld", "pd", "Lq", "dim", "K", "nbits")


@pytest.fixture(scope="module")
def base_n(small_corpus):
    return small_corpus["cfg"].n_docs - HOLD


@pytest.fixture
def retr(tmp_path, small_corpus, base_n):
    c = small_corpus
    build_colbert_index(tmp_path / "colbert", c["doc_embs"][:base_n],
                        c["doc_lens"][:base_n], nbits=2, n_centroids=64,
                        kmeans_iters=2)
    build_splade_index(c["doc_term_ids"][:base_n],
                       c["doc_term_weights"][:base_n], c["cfg"].vocab,
                       base_n).save(tmp_path / "splade")
    return MultiStageRetriever(
        SpladeIndex.load(tmp_path / "splade", mmap=True),
        PLAIDSearcher(ColBERTIndex(tmp_path / "colbert", mode="mmap"),
                      PlaidParams(nprobe=4, candidate_cap=512, ndocs=64)),
        MultiStageParams(first_k=32, k=10))


def _queries(c, rows):
    """Queries carrying the terms of passages ``rows``, so that stage 1
    puts those passages among the candidates."""
    return dict(q_embs=[c["q_embs"][i % len(c["q_embs"])]
                        for i in range(len(rows))],
                term_ids=[c["doc_term_ids"][r][:6] for r in rows],
                term_weights=[c["doc_term_weights"][r][:6] for r in rows])


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler → the live path's host spans
    (``stage:live_*``, ``live:*``) as (name, stats)."""
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    found = sorted(pathlib.Path(tmp_path / "trace").glob("**/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(found[-1]))
    return [(e.name, {k: v for k, v in e.stats})
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith(("stage:live_", "live:"))]


def test_overlay_and_write_spans_records_and_counters(retr, small_corpus,
                                                       base_n, tmp_path):
    c = small_corpus
    retr.enable_live()
    stats = retr.pipeline_stats
    rows = list(range(base_n, base_n + HOLD))
    q = _queries(c, rows)
    ctxs = [RequestContext(qid=100 + i, method="hybrid", k=10)
            for i in range(len(rows))]

    def work():
        for j in rows:
            retr.live_upsert(c["doc_embs"][j], c["doc_term_ids"][j],
                             c["doc_term_weights"][j], c["doc_lens"][j])
        assert retr.live_delete(3)
        retr.search_batch_ctx("hybrid", ctxs=ctxs, **q, k=10)
        retr.compact_live()
    events = _traced(tmp_path, work)
    names = [n for n, _ in events]
    for name in OVERLAY + WRITES:
        assert name in names, name
    assert names.count("live:upsert") == HOLD
    upserts = [s for n, s in events if n == "live:upsert"]
    assert sorted(s["doc_len"] for s in upserts) == sorted(
        int(c["doc_lens"][j]) for j in rows)
    overlay = [s for n, s in events if n == "stage:live_overlay"]
    assert [int(x) for x in str(overlay[0]["qids"]).split()] == [
        100, 101, 102, 103]
    # the delta dispatch scores its few candidates at the narrow width
    for name, width in (("stage:live_base_exact", 32),
                        ("stage:live_delta", DELTA_WIDTH)):
        s = dict(events)[name]
        assert {k: s[k] for k in SHAPES} == {
            "B": 4, "C": width, "Ld": retr.live.doc_maxlen,
            "pd": retr.live.packed_dim, "Lq": 8,     # 6 tokens padded
            "dim": c["cfg"].dim, "K": 64, "nbits": 2}

    snap = stats.snapshot()
    for name in OVERLAY + WRITES:
        rec = snap["stages"][name.removeprefix("stage:").replace(":", "_")]
        assert rec["dispatches"] >= 1 and rec["wall_s"] > 0, name
    assert snap["stages"]["live_overlay"]["queries"] == HOLD
    n = snap["counters"]
    assert (n["live_upserts"], n["live_deletes"], n["live_compactions"]) \
        == (HOLD, 1, 1)
    # the batch's queries carry the upserted passages' own terms
    assert HOLD <= n["delta_rows_scored"] <= HOLD * 32
    assert n["live_delta_narrow"] == 1 and "live_delta_full" not in n
    # gauges: the delta went into the base; the tombstone stays
    assert (n["delta_docs"], n["tombstones"]) == (0, 1)


def test_gauges_read_at_each_snapshot_and_in_health(retr, small_corpus,
                                                    base_n):
    c = small_corpus
    retr.enable_live()
    retr.reset_stage_stats()
    assert retr.pipeline_stats.snapshot()["counters"] == {
        "delta_docs": 0, "tombstones": 0}
    retr.live_upsert(c["doc_embs"][base_n], c["doc_term_ids"][base_n],
                     c["doc_term_weights"][base_n], c["doc_lens"][base_n])
    retr.live_delete(base_n)
    retr.live_delete(base_n)             # already deleted: not counted
    retr.reset_stage_stats()             # gauges outlive a reset
    n = retr.pipeline_stats.snapshot()["counters"]
    assert (n["delta_docs"], n["tombstones"]) == (1, 1)
    assert "live_deletes" not in n
    engine = ServeEngine(retr)
    try:
        h = RetrievalServer(engine).health()["counters"]
    finally:
        engine.close()
    assert (h["delta_docs"], h["tombstones"]) == (1, 1)


@pytest.mark.parametrize("enabled", [False, True])
def test_frozen_path_runs_no_live_span_or_counter(retr, small_corpus,
                                                  tmp_path, enabled):
    """A frozen index, and a live one with no write yet, serve through
    the compiled plans: no ``live`` span, record or counter (a clean
    live index reads its gauges, both 0)."""
    if enabled:
        retr.enable_live()
    q = _queries(small_corpus, [0, 1])
    for m in ("hybrid", "colbert"):
        retr.search_batch(m, **q, k=10)
    retr.reset_stage_stats()
    events = _traced(tmp_path, lambda: [retr.search_batch(m, **q, k=10)
                                        for m in ("hybrid", "colbert")])
    assert events == []
    snap = retr.pipeline_stats.snapshot()
    assert not [s for s in snap["stages"] if "live" in s]
    assert snap["counters"].get("delta_docs", 0) == 0
    assert not [k for k in snap["counters"]
                if k.startswith("live_") or k == "delta_rows_scored"]
    assert not {"live_delta_narrow", "live_delta_full"} & set(
        snap["counters"])


def _serve(retr, rows_per_batch, q):
    for rows in rows_per_batch:
        retr.search_batch("hybrid", k=10, **{
            key: [v[r] for r in rows] for key, v in q.items()})


def test_delta_span_width_and_counters_narrow_then_full(retr, small_corpus,
                                                        base_n, tmp_path):
    """``stage:live_delta`` carries the width of the dispatch it makes:
    the narrow width while every row's delta candidates fit in it, the
    full candidate width once a row holds more; ``live_delta_narrow``
    and ``live_delta_full`` count each, in snapshots and in
    ``health()``."""
    c = small_corpus
    retr.enable_live()
    retr.live_upsert(c["doc_embs"][base_n], c["doc_term_ids"][base_n],
                     c["doc_term_weights"][base_n], c["doc_lens"][base_n])
    q = _queries(c, [base_n])
    events = _traced(tmp_path / "narrow", lambda: _serve(retr, [[0]], q))
    # more copies of the passage than the narrow width holds: its query
    # now has them all among its 32 candidates
    for _ in range(DELTA_WIDTH):
        retr.live_upsert(c["doc_embs"][base_n], c["doc_term_ids"][base_n],
                         c["doc_term_weights"][base_n],
                         c["doc_lens"][base_n])
    events += _traced(tmp_path / "full", lambda: _serve(retr, [[0]], q))
    widths = [s["C"] for n, s in events if n == "stage:live_delta"]
    assert widths == [DELTA_WIDTH, 32]
    n = retr.pipeline_stats.snapshot()["counters"]
    assert (n["live_delta_narrow"], n["live_delta_full"]) == (1, 1)
    assert n["delta_rows_scored"] == 1 + DELTA_WIDTH + 1
    engine = ServeEngine(retr)
    try:
        h = RetrievalServer(engine).health()["counters"]
    finally:
        engine.close()
    assert (h["live_delta_narrow"], h["live_delta_full"]) == (1, 1)


def test_warm_overlay_serves_every_batch_size_without_compiling(
        retr, small_corpus, base_n):
    """Warm-up as the benchmark's (the hybrid overlay at B = 1..8 on the
    upserted passages' terms) compiles every program the dirty overlay
    dispatches: narrow delta batches at every B, and full-width ones
    (the base dispatch's program), compile nothing more."""
    c = small_corpus
    retr.enable_live()
    rows = list(range(base_n, base_n + HOLD))
    for j in rows:
        retr.live_upsert(c["doc_embs"][j], c["doc_term_ids"][j],
                         c["doc_term_weights"][j], c["doc_lens"][j])
    q = _queries(c, [rows[i % HOLD] for i in range(8)])
    _serve(retr, [range(b) for b in range(1, 9)], q)
    before = retr.pipeline_stats.snapshot()["counters"]
    # other passages' terms, then a batch whose first row holds more
    # delta candidates than the narrow width (copies of one passage)
    _serve(retr, [[(i + 1) % 8 for i in range(b)] for b in range(1, 9)], q)
    for _ in range(DELTA_WIDTH):
        retr.live_upsert(c["doc_embs"][rows[0]], c["doc_term_ids"][rows[0]],
                         c["doc_term_weights"][rows[0]],
                         c["doc_lens"][rows[0]])
    _serve(retr, [range(b) for b in range(1, 9)], q)
    after = retr.pipeline_stats.snapshot()["counters"]
    assert after.get("jax_compiles", 0) == before.get("jax_compiles", 0)
    assert after["live_delta_narrow"] - before["live_delta_narrow"] >= 8
    assert after["live_delta_full"] - before.get("live_delta_full", 0) == 8
