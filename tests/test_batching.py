"""Cross-query batched execution: a batch row == the same query run
alone (B = 1) for every stage (kernels, PLAID plan, multi-stage methods,
server micro-batcher), stage-3 codes-only access, and shutdown
semantics."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams, pad_query_batch
from repro.index.builder import ColBERTIndex
from repro.index.splade_index import build_splade_index
from repro.kernels.decompress_maxsim.ops import (
    decompress_maxsim_scores,
    decompress_maxsim_scores_batch,
)
from repro.kernels.maxsim.ops import maxsim_scores, maxsim_scores_batch
from repro.serving.engine import Request, ServeEngine
from repro.serving.server import RetrievalServer

METHODS = ("colbert", "splade", "rerank", "hybrid")


# ---------------------------------------------------------------------------
# batched kernels == per-query loop
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl,block_c", [("ref", 16), ("interpret", 4)])
def test_maxsim_batch_equals_loop(impl, block_c):
    B, C, Ld, Lq, d = 3, 20, 12, 8, 32
    k = jax.random.PRNGKey(0)
    q = jax.random.normal(k, (B, Lq, d))
    docs = jax.random.normal(jax.random.fold_in(k, 1), (B, C, Ld, d))
    dv = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.8, (B, C, Ld))
    qv = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.9, (B, Lq))
    batch = maxsim_scores_batch(q, docs, dv, qv, impl=impl, block_c=block_c)
    loop = jnp.stack([maxsim_scores(q[b], docs[b], dv[b], qv[b], impl="ref")
                      for b in range(B)])
    np.testing.assert_allclose(np.asarray(batch), np.asarray(loop),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_decompress_maxsim_batch_equals_loop(impl):
    B, C, Ld, Lq, d, nbits, K = 3, 20, 12, 8, 32, 4, 16
    k = jax.random.PRNGKey(1)
    q = jax.random.normal(k, (B, Lq, d))
    packed = jax.random.randint(jax.random.fold_in(k, 1),
                                (B, C, Ld, d * nbits // 8), 0, 256
                                ).astype(jnp.uint8)
    cids = jax.random.randint(jax.random.fold_in(k, 2), (B, C, Ld), 0, K)
    dv = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.85, (B, C, Ld))
    qv = jax.random.bernoulli(jax.random.fold_in(k, 4), 0.9, (B, Lq))
    cent = jax.random.normal(jax.random.fold_in(k, 5), (K, d))
    bw = jnp.linspace(-0.3, 0.3, 2 ** nbits)
    batch = decompress_maxsim_scores_batch(q, packed, cids, dv, cent, bw,
                                           nbits=nbits, q_valid=qv,
                                           impl=impl)
    loop = jnp.stack([decompress_maxsim_scores(q[b], packed[b], cids[b],
                                               dv[b], cent, bw, nbits=nbits,
                                               q_valid=qv[b], impl="ref")
                      for b in range(B)])
    np.testing.assert_allclose(np.asarray(batch), np.asarray(loop),
                               rtol=1e-3, atol=1e-3)


def test_pad_query_batch_ragged():
    qs = [np.ones((4, 8), np.float32), np.ones((2, 8), np.float32)]
    q, valid = pad_query_batch(qs)
    assert q.shape == (2, 4, 8)
    assert np.asarray(valid).tolist() == [[True] * 4,
                                          [True, True, False, False]]
    np.testing.assert_array_equal(np.asarray(q[1, 2:]), 0.0)


# ---------------------------------------------------------------------------
# PLAID / multistage stack
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def stack(built_index, small_corpus):
    index = ColBERTIndex(built_index, mode="mmap")
    searcher = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                                ndocs=128, k=50))
    sidx = build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)
    retr = MultiStageRetriever(sidx, searcher,
                               MultiStageParams(first_k=50, k=20))
    return index, searcher, retr


def _ragged_queries(small_corpus, n):
    """Per-query embeddings with deliberately ragged lengths."""
    lens = (6, 4, 6, 5, 3, 6, 2, 5)
    return [small_corpus["q_embs"][i][:lens[i % len(lens)]]
            for i in range(n)]


def _plaid_plan(retr, qs, k):
    """PLAID (``colbert``) through the retriever's compiled stage plan
    → (pids (B, k), scores (B, k), per-query aux)."""
    cb = retr.compile_plan("colbert").run(
        retr.build_batch("colbert", q_embs=qs, k=k))
    return cb.pids, cb.scores, cb.state["aux"]


def _score_candidates(searcher, qs, pids):
    """Exact MaxSim of each query against its own candidate row (−1 pad
    → -inf), through the residual gather and rerank scoring the plans
    run → host (B, C)."""
    q, q_valid = pad_query_batch(qs)
    pids = np.asarray(pids)
    codes, packed, valid = searcher.gather_tokens_batch(pids)
    return np.asarray(searcher.score_gathered_lazy(q, q_valid, codes,
                                                   packed, valid, pids))


def test_search_batch_equals_sequential_ragged(stack, small_corpus):
    _, _, retr = stack
    qs = _ragged_queries(small_corpus, 6)
    bp, bs, aux = _plaid_plan(retr, qs, 20)
    for i, q in enumerate(qs):
        sp, ss, a = _plaid_plan(retr, [q], 20)
        np.testing.assert_array_equal(bp[i], sp[0])
        np.testing.assert_allclose(bs[i], ss[0], rtol=1e-4, atol=1e-4)
        assert aux[i]["candidates"] == a[0]["candidates"]


def test_search_batch_device_resident(built_index, small_corpus, stack):
    index = ColBERTIndex(built_index, mode="ram")
    dev = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                           ndocs=128, k=50),
                        device_resident=True)
    retr = MultiStageRetriever(stack[2].splade, dev)
    qs = _ragged_queries(small_corpus, 4)
    bp, bs, _ = _plaid_plan(retr, qs, 15)
    for i, q in enumerate(qs):
        sp, ss, _ = _plaid_plan(retr, [q], 15)
        np.testing.assert_array_equal(bp[i], sp[0])
        np.testing.assert_allclose(bs[i], ss[0], rtol=1e-4, atol=1e-4)


def test_rerank_batch_equals_sequential(stack, small_corpus):
    _, searcher, _ = stack
    qs = _ragged_queries(small_corpus, 3)
    pids = np.stack([np.arange(30), np.arange(30) + 5,
                     np.concatenate([np.arange(20), np.full(10, -1)])])
    batch = _score_candidates(searcher, qs, pids)
    for i, q in enumerate(qs):
        np.testing.assert_allclose(
            batch[i], _score_candidates(searcher, [q], pids[i:i + 1])[0],
            rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("method", METHODS)
def test_multistage_batch_equals_sequential(stack, small_corpus, method):
    _, _, retr = stack
    B = 5
    args = dict(
        q_embs=[small_corpus["q_embs"][i] for i in range(B)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(B)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(B)])
    bp, bs = retr.search_batch(method, k=15, **args)
    for i in range(B):
        sp, ss = retr.search(method, q_emb=args["q_embs"][i],
                             term_ids=args["term_ids"][i],
                             term_weights=args["term_weights"][i], k=15)
        np.testing.assert_array_equal(bp[i], sp)
        np.testing.assert_allclose(bs[i], ss, rtol=1e-3, atol=1e-3)


def test_multistage_batch_mixed_methods(stack, small_corpus):
    _, _, retr = stack
    methods = ["hybrid", "colbert", "rerank", "splade", "hybrid", "rerank"]
    alphas = [0.2, None, None, None, 0.7, None]
    n = len(methods)
    args = dict(
        q_embs=[small_corpus["q_embs"][i] for i in range(n)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(n)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(n)])
    bp, bs = retr.search_batch(methods, alpha=alphas, k=10, **args)
    for i, m in enumerate(methods):
        sp, ss = retr.search(m, q_emb=args["q_embs"][i],
                             term_ids=args["term_ids"][i],
                             term_weights=args["term_weights"][i],
                             alpha=alphas[i], k=10)
        np.testing.assert_array_equal(bp[i], sp)
        np.testing.assert_allclose(bs[i], ss, rtol=1e-3, atol=1e-3)


def test_hybrid_scores_with_neg_inf_padding_stay_finite():
    """-inf at padded slots (rerank scores of -1 pids) must not poison
    the masked normalisation stats with NaN."""
    from repro.core.hybrid import hybrid_scores
    s = jnp.asarray([3.0, 2.0, 0.0])
    c = jnp.asarray([5.0, 4.0, -jnp.inf])
    mask = jnp.asarray([True, True, False])
    out = np.asarray(hybrid_scores(s, c, mask, alpha=0.3))
    assert np.isfinite(out[:2]).all(), out
    assert np.isneginf(out[2])


def test_mixed_batch_k_beyond_first_k(stack, small_corpus):
    """k > first_k in a mixed batch: splade-first groups fill only
    min(k, first_k) columns; the rest is (-1, -inf) padding, and the
    colbert group fills its full k."""
    _, _, retr = stack
    first_k = retr.params.first_k
    k = first_k + 10
    methods = ["colbert", "hybrid"]
    bp, bs = retr.search_batch(
        methods, k=k,
        q_embs=[small_corpus["q_embs"][i] for i in range(2)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(2)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(2)])
    assert bp.shape == (2, k)
    assert (bp[1, first_k:] == -1).all()
    assert np.isneginf(bs[1, first_k:]).all()


def test_k_zero_and_explicit_k_honored(stack, small_corpus):
    """A k=0 request must not silently become k=params.k (regression for
    the falsy ``k or p.k`` default)."""
    _, _, retr = stack
    q = small_corpus["q_embs"][0]
    pids, scores = retr.search("colbert", q_emb=q, k=0)
    assert pids.shape == (0,) and scores.shape == (0,)
    sp, ss = retr.search("hybrid", q_emb=q,
                         term_ids=small_corpus["q_term_ids"][0],
                         term_weights=small_corpus["q_term_weights"][0], k=0)
    assert sp.shape == (0,)
    bp, bs = retr.search_batch("colbert", q_embs=[q, q], k=0)
    assert bp.shape == (2, 0)


# ---------------------------------------------------------------------------
# stage-3 access minimisation (the paper's claim, now enforced)
# ---------------------------------------------------------------------------

def test_codes_only_gather_touches_zero_residual_pages(stack):
    index, _, _ = stack
    index.store.stats.reset()
    index.gather_doc_codes(np.arange(32))
    st = index.store.stats
    assert st.gathers == 1 and st.tokens_read > 0
    assert st.pages_touched == 0
    assert len(st.unique_pages) == 0
    assert st.residual_gathers == 0
    assert st.residual_tokens_read == 0


def test_stage3_touches_zero_residual_pages(stack, small_corpus):
    """Full mmap search faults residual pages in stage 4 ONLY: exactly
    one residual gather, covering the survivors (at most ``ndocs``) —
    stages 1-3 stay codes-only."""
    index, searcher, retr = stack
    index.store.stats.reset()
    _, _, aux = _plaid_plan(retr, [small_corpus["q_embs"][0]], 10)
    st = index.store.stats
    cand = aux[0]["candidates"]
    assert st.residual_gathers == 1
    assert st.residual_tokens_read == \
        min(searcher.params.ndocs, cand) * index.doc_maxlen
    # the codes-only stage-3 gather still happened (and was accounted):
    # one row of codes per candidate
    assert st.gathers == 2
    assert st.tokens_read - st.residual_tokens_read == \
        cand * index.doc_maxlen


def test_batched_gathers_share_pages(stack, small_corpus):
    """Duplicate queries co-batched touch the same residual pages once —
    the shared-page-touch benefit the micro-batcher exists for."""
    index, _, retr = stack
    q = small_corpus["q_embs"][1]
    index.store.stats.reset()
    retr.search("colbert", q_emb=q, k=10)
    single = index.store.stats.pages_touched
    index.store.stats.reset()
    retr.search_batch("colbert", q_embs=[q, q, q], k=10)
    batched = index.store.stats.pages_touched
    assert batched == single


# ---------------------------------------------------------------------------
# server-level micro-batching
# ---------------------------------------------------------------------------

def _requests(small_corpus, n, k=10):
    return [Request(qid=i, method=METHODS[i % len(METHODS)],
                    q_emb=small_corpus["q_embs"][i],
                    term_ids=small_corpus["q_term_ids"][i],
                    term_weights=small_corpus["q_term_weights"][i], k=k)
            for i in range(n)]


def test_server_microbatch_equals_sequential(stack, small_corpus):
    _, _, retr = stack
    n = 16
    seq_srv = RetrievalServer(ServeEngine(retr), n_threads=1)
    seq_srv.start()
    seq = [seq_srv.submit(r).result(timeout=60)
           for r in _requests(small_corpus, n)]
    seq_srv.stop()

    bat_srv = RetrievalServer(ServeEngine(retr), n_threads=1, max_batch=8,
                              batch_timeout_ms=25)
    # queued before start(): the worker takes them as two full batches
    futs = [bat_srv.submit(r) for r in _requests(small_corpus, n)]
    bat_srv.start()
    bat = [f.result(timeout=60) for f in futs]
    assert bat_srv.health()["served"] == n
    bat_srv.stop()

    for r_seq, r_bat in zip(seq, bat):
        assert r_seq.qid == r_bat.qid
        np.testing.assert_array_equal(r_seq.pids, r_bat.pids)
        np.testing.assert_allclose(r_seq.scores, r_bat.scores,
                                   rtol=1e-3, atol=1e-3)


def test_microbatch_respects_per_request_k(stack, small_corpus):
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr), n_threads=1, max_batch=4,
                          batch_timeout_ms=25)
    reqs = _requests(small_corpus, 4, k=10)
    for r, want in zip(reqs, (3, 10, 7, 1)):
        r.k = want
    futs = [srv.submit(r) for r in reqs]     # one batch: queued first
    srv.start()
    for r, fut in zip(reqs, futs):
        assert len(fut.result(timeout=60).pids) == r.k
    srv.stop()


def test_stop_fails_queued_futures(stack, small_corpus):
    """stop() must not leave enqueued-but-unserved futures pending."""
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr), n_threads=1)
    # never started: nothing drains the queue
    futs = [srv.submit(r) for r in _requests(small_corpus, 3)]
    srv.stop()
    for fut in futs:
        assert fut.done()
        with pytest.raises(RuntimeError, match="server stopped"):
            fut.result(timeout=1)


def test_cancelled_future_does_not_kill_worker(stack, small_corpus):
    """A client cancelling a queued request must not crash the worker or
    disturb co-batched neighbours (regression: double-resolution raised
    InvalidStateError inside the worker thread)."""
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr), n_threads=1, max_batch=4,
                          batch_timeout_ms=25)
    futs = [srv.submit(r) for r in _requests(small_corpus, 4)]
    assert futs[1].cancel()          # cancelled while still queued
    srv.start()
    for i in (0, 2, 3):
        assert len(futs[i].result(timeout=60).pids) > 0
    # worker survived and keeps serving
    extra = srv.submit(_requests(small_corpus, 1)[0])
    assert len(extra.result(timeout=60).pids) > 0
    assert srv.health()["workers"] == 1
    srv.stop()


def test_microbatch_isolates_poisoned_request(stack, small_corpus):
    """One bad request in a coalesced batch fails alone; its co-batched
    neighbours still succeed."""
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr), n_threads=1, max_batch=4,
                          batch_timeout_ms=25)
    reqs = _requests(small_corpus, 4)
    reqs[2].method = "no-such-method"
    futs = [srv.submit(r) for r in reqs]     # one batch: queued first
    srv.start()
    with pytest.raises(ValueError):
        futs[2].result(timeout=60)
    for i in (0, 1, 3):
        assert len(futs[i].result(timeout=60).pids) > 0
    assert srv.health()["failed"] == 1
    srv.stop()
