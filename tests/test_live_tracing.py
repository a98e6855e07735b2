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
    for name in ("stage:live_base_exact", "stage:live_delta"):
        s = dict(events)[name]
        assert {k: s[k] for k in SHAPES} == {
            "B": 4, "C": 32, "Ld": retr.live.doc_maxlen,
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
