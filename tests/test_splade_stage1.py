"""Batched device-resident SPLADE stage 1: backend parity
(host CSR == vectorised batch host == JAX segment-sum == batched Pallas
kernel in interpret mode), padded-postings truncation semantics, edge
cases (zero-weight queries, k > n_docs), the no-per-query-loop
guarantee for jax/pallas `search_batch`, and adaptive micro-batch
sizing in the server."""

import time

import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.index.builder import ColBERTIndex
from repro.index.splade_device import SpladeDeviceCache
from repro.index.splade_index import SpladeIndex, build_splade_index
from repro.serving.engine import Request, Result, ServeEngine
from repro.serving.server import RetrievalServer


@pytest.fixture(scope="module")
def sidx(small_corpus):
    return build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)


@pytest.fixture(scope="module")
def queries(small_corpus):
    rng = np.random.default_rng(5)
    tids, tw = [], []
    for i in range(6):
        n = int(rng.integers(2, 8))
        tids.append(small_corpus["q_term_ids"][i][:n])
        tw.append(small_corpus["q_term_weights"][i][:n])
    return tids, tw


def test_topk_rows_tie_break_matches_stable_argsort():
    """The O(n) partition+refine selection must be indistinguishable
    from a stable full argsort (score desc, pid asc) — the order
    ``lax.top_k`` uses, and what shard-merge parity relies on. Heavy
    integer ties exercise both the boundary fill and the final sort."""
    from repro.index.splade_index import _topk_rows
    rng = np.random.default_rng(9)
    scores = rng.integers(0, 6, (5, 97)).astype(np.float32)
    scores[1] = 0.0                              # all-tied row
    for k in (1, 7, 50, 97, 120):
        got_p, got_s = _topk_rows(scores, k)
        ref = np.argsort(-scores, axis=1, kind="stable")[:, :min(k, 97)]
        np.testing.assert_array_equal(got_p[:, :ref.shape[1]], ref)
        np.testing.assert_array_equal(
            got_s[:, :ref.shape[1]],
            np.take_along_axis(scores, ref, axis=1))
        assert (got_p[:, ref.shape[1]:] == -1).all()
        assert (got_s[:, ref.shape[1]:] == 0).all()


# ---------------------------------------------------------------------------
# host scoring: the np.add.at regression + vectorised batch parity
# ---------------------------------------------------------------------------

def test_score_host_accumulates_duplicate_pids():
    """A doc listing the same term twice yields two postings with the
    same pid; fancy-index += silently dropped one of them."""
    ids = np.array([[7, 7, 3]], np.int32)
    w = np.array([[1.0, 1.0, 2.0]], np.float32)
    idx = build_splade_index(ids, w, vocab=10, n_docs=1)
    s, e = idx.term_offsets[7], idx.term_offsets[8]
    assert e - s == 2 and (idx.pids[s:e] == 0).all()   # duplicate-pid term
    pids, scores = idx.score_host(np.array([7], np.int32),
                                  np.array([1.0], np.float32), k=1)
    expected = (idx.impacts[s:e].astype(np.float32) * idx.quantum).sum()
    np.testing.assert_allclose(scores[0], expected, rtol=1e-5)


def test_score_batch_host_matches_score_host(sidx, queries):
    tids, tw = queries
    bp, bs = sidx.score_batch_host(tids, tw, k=25)
    for i in range(len(tids)):
        sp, ss = sidx.score_host(tids[i], tw[i], k=25)
        np.testing.assert_array_equal(bp[i], sp)
        np.testing.assert_array_equal(bs[i], ss)


def test_score_batch_host_shares_union_gathers(sidx, queries):
    """Duplicate queries co-batched score identically to one copy (the
    union-of-terms pass must not double-count shared terms)."""
    tids, tw = queries
    dup_p, dup_s = sidx.score_batch_host([tids[0], tids[0]],
                                         [tw[0], tw[0]], k=10)
    np.testing.assert_array_equal(dup_p[0], dup_p[1])
    np.testing.assert_array_equal(dup_s[0], dup_s[1])


# ---------------------------------------------------------------------------
# backend parity: host == jax segment-sum == batched pallas (interpret)
# ---------------------------------------------------------------------------

def test_backend_parity_host_jax_pallas_interpret(sidx, queries):
    tids, tw = queries
    hp, hs = sidx.score_batch_host(tids, tw, k=30)
    cache = SpladeDeviceCache(sidx)          # max_df=None → exact
    assert cache.truncated_terms == 0
    jp, js = cache.score_topk(tids, tw, k=30, impl="ref")
    pp, ps = cache.score_topk(tids, tw, k=30, impl="interpret")
    np.testing.assert_allclose(js, hs, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(ps, js, rtol=1e-4, atol=1e-4)
    # same candidate sets at every rank with distinct scores
    np.testing.assert_array_equal(jp, pp)


def test_padded_truncation_keeps_top_impacts():
    """df > max_df: the device tier keeps the top-impact postings, so
    truncated scores lower-bound exact scores and match a manual
    top-max_df recomputation."""
    n_docs, term = 12, 0
    ids = np.zeros((n_docs, 1), np.int32)          # every doc has term 0
    w = (np.arange(1, n_docs + 1, dtype=np.float32)
         .reshape(n_docs, 1))                      # distinct impacts
    idx = build_splade_index(ids, w, vocab=4, n_docs=n_docs)
    cache = SpladeDeviceCache(idx, max_df=4)
    assert cache.max_df == 4 and cache.truncated_terms == 1
    q = [np.array([term], np.int32)], [np.array([1.0], np.float32)]
    tp, ts = cache.score_topk(q[0], q[1], k=n_docs, impl="ref")
    ep, es = idx.score_batch_host(q[0], q[1], k=n_docs)
    # kept: the 4 highest-impact docs, scored exactly as the host tier
    np.testing.assert_array_equal(np.sort(tp[0, :4]), np.sort(ep[0, :4]))
    np.testing.assert_allclose(ts[0, :4], es[0, :4], rtol=1e-4)
    # dropped postings score 0, never inflated
    assert (ts[0, 4:] == 0).all()
    assert (es[0, 4:] > 0).all()


def test_all_zero_weight_query(sidx):
    tids = [np.array([1, 2, 3], np.int32)]
    tw = [np.zeros(3, np.float32)]
    hp, hs = sidx.score_batch_host(tids, tw, k=5)
    assert (hs == 0).all()
    cache = SpladeDeviceCache(sidx)
    for impl in ("ref", "interpret"):
        dp, ds = cache.score_topk(tids, tw, k=5, impl=impl)
        assert (ds == 0).all(), impl
        assert np.isfinite(ds).all()


def test_out_of_vocab_term_rejected(sidx):
    """The device tier must fail loudly like the host CSR path — a
    clamped gather would silently return the last term's postings."""
    cache = SpladeDeviceCache(sidx)
    bad = [np.array([sidx.vocab + 3], np.int32)]
    w = [np.array([1.0], np.float32)]
    with pytest.raises(IndexError, match="out of range"):
        cache.score_topk(bad, w, k=5, impl="ref")
    with pytest.raises(IndexError):
        sidx.score_host(bad[0], w[0], k=5)


def test_k_gt_n_docs(sidx, queries):
    tids, tw = queries
    k = sidx.n_docs + 13
    hp, hs = sidx.score_batch_host(tids[:2], tw[:2], k=k)
    assert hp.shape == (2, k)
    assert (hp[:, sidx.n_docs:] == -1).all()
    assert (hs[:, sidx.n_docs:] == 0).all()
    cache = SpladeDeviceCache(sidx)
    dp, ds = cache.score_topk(tids[:2], tw[:2], k=k, impl="ref")
    assert dp.shape == (2, k)
    assert (dp[:, sidx.n_docs:] == -1).all()
    np.testing.assert_allclose(ds[:, :sidx.n_docs], hs[:, :sidx.n_docs],
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# retriever integration: single dispatch, no per-query host loop
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def retr(built_index, small_corpus, sidx):
    index = ColBERTIndex(built_index, mode="mmap")
    searcher = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                                ndocs=128, k=50))
    return MultiStageRetriever(sidx, searcher,
                               MultiStageParams(first_k=50, k=20))


def _interpret_off_tpu(self, backend):
    # the program refuses the Mosaic kernel off-TPU; tests run its body
    # in interpret mode instead
    return "ref" if backend == "jax" else "interpret"


def test_pallas_backend_raises_without_tpu(retr):
    with pytest.raises(RuntimeError, match="needs a TPU"):
        retr.set_splade_backend("pallas")
    assert retr.splade_backend == "host"
    with pytest.raises(RuntimeError, match="needs a TPU"):
        retr.run_splade_batch([np.array([1], np.int32)],
                              [np.array([1.0], np.float32)], k=5,
                              backend="pallas")


@pytest.mark.parametrize("backend", ["jax", "pallas"])
@pytest.mark.parametrize("method", ["splade", "rerank", "hybrid"])
def test_search_batch_device_backend_matches_search(retr, small_corpus,
                                                    backend, method,
                                                    monkeypatch):
    B = 5
    monkeypatch.setattr(MultiStageRetriever, "_splade_impl",
                        _interpret_off_tpu)
    args = dict(
        q_embs=[small_corpus["q_embs"][i] for i in range(B)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(B)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(B)])
    retr.set_splade_backend(backend)
    try:
        sequential = [retr.search(method, q_emb=args["q_embs"][i],
                                  term_ids=args["term_ids"][i],
                                  term_weights=args["term_weights"][i],
                                  k=15)
                      for i in range(B)]
        # the batched path must never fall back to the per-query host CSR
        # loop, and must issue exactly ONE stage-1 dispatch
        monkeypatch.setattr(
            SpladeIndex, "score_host",
            lambda *a, **k: pytest.fail("per-query score_host called "
                                        "on a device backend"))
        retr.reset_stage_stats()
        bp, bs = retr.search_batch(method, k=15, **args)
        assert retr.stage_stats["stage1_dispatches"] == 1
        assert retr.stage_stats["stage1_queries"] == B
    finally:
        retr.set_splade_backend("host")
    for i, (sp, ss) in enumerate(sequential):
        np.testing.assert_array_equal(bp[i], sp)
        np.testing.assert_allclose(bs[i], ss, rtol=1e-3, atol=1e-3)


def test_search_batch_host_backend_is_single_pass(retr, small_corpus):
    """The host backend also batches: one vectorised dispatch, no
    per-query loop in search_batch."""
    B = 4
    retr.reset_stage_stats()
    retr.search_batch(
        "splade", k=10,
        q_embs=[small_corpus["q_embs"][i] for i in range(B)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(B)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(B)])
    assert retr.stage_stats["stage1_dispatches"] == 1
    assert retr.stage_stats["stage1_queries"] == B


def test_engine_backend_override(retr, small_corpus):
    """The retriever owns the stage-1 backend: switched to ``jax``, an
    engine over it scores stage 1 from the padded-postings device
    cache."""
    assert retr.splade_backend == "host"
    retr.set_splade_backend("jax")
    retr._splade_device = None
    try:
        assert retr.splade_backend == "jax"
        ServeEngine(retr).process(Request(
            qid=0, method="splade", term_ids=small_corpus["q_term_ids"][0],
            term_weights=small_corpus["q_term_weights"][0], k=5))
        assert retr._splade_device is not None    # cache materialised
    finally:
        retr.set_splade_backend("host")


def test_unknown_backend_rejected(retr):
    with pytest.raises(ValueError, match="backend"):
        retr.set_splade_backend("cuda")
    with pytest.raises(ValueError, match="backend"):
        retr.run_splade_batch([np.array([1])], [np.array([1.0])],
                              backend="cuda")


# ---------------------------------------------------------------------------
# adaptive micro-batch sizing (latency SLO)
# ---------------------------------------------------------------------------

class _PacedEngine:
    """Engine stub whose service time is settable at runtime."""

    def __init__(self):
        self.served = 0
        self.delay_s = 0.0

    def _result(self, req):
        now = time.perf_counter()
        return Result(qid=req.qid, pids=np.array([0]),
                      scores=np.array([1.0]), t_arrival=req.t_arrival,
                      t_start=now, t_done=now + self.delay_s)

    def process(self, req):
        time.sleep(self.delay_s)
        self.served += 1
        return self._result(req)

    def process_batch(self, reqs):
        time.sleep(self.delay_s)
        self.served += len(reqs)
        return [self._result(r) for r in reqs]


def _drain(srv, n):
    """Serve ``n`` requests queued while the worker is stopped, so the
    batches it forms fill the cap as it stands."""
    srv.stop()
    futs = [srv.submit(Request(qid=i, method="splade")) for i in range(n)]
    srv.start()
    for f in futs:
        f.result(timeout=30)


def test_adaptive_batch_cap_shrinks_then_recovers():
    eng = _PacedEngine()
    srv = RetrievalServer(eng, n_threads=1, max_batch=8,
                          batch_timeout_ms=1.0, latency_slo_ms=20.0,
                          slo_ewma_alpha=1.0)   # react instantly
    srv.start()
    try:
        assert srv.batch_cap == 8
        eng.delay_s = 0.06                      # 60ms ≫ 20ms SLO
        _drain(srv, 12)
        assert srv.batch_cap < 8
        assert srv.health()["ewma_latency_ms"] > 20.0
        shrunk = srv.batch_cap
        eng.delay_s = 0.0                       # latency collapses
        _drain(srv, 40)
        assert srv.batch_cap > shrunk
    finally:
        srv.stop()


def test_fixed_cap_without_slo():
    eng = _PacedEngine()
    eng.delay_s = 0.03
    srv = RetrievalServer(eng, n_threads=1, max_batch=4,
                          batch_timeout_ms=1.0)   # no latency_slo_ms
    srv.start()
    try:
        _drain(srv, 8)
        assert srv.batch_cap == 4                 # never adapted
        assert srv.health()["ewma_latency_ms"] is None
    finally:
        srv.stop()
