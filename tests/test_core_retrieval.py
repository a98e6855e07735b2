"""The paper's core: PLAID multi-stage search, hybrid scoring,
multi-stage pipeline quality ordering, mmap access minimisation."""

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import hybrid as H
from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams, pad_query_batch
from repro.eval import metrics
from repro.index.builder import ColBERTIndex
from repro.index.residual import decode_embeddings
from repro.index.splade_index import build_splade_index
from repro.kernels.maxsim.ref import maxsim_scores_ref


# ---------------------------------------------------------------------------
# hybrid normalisers
# ---------------------------------------------------------------------------

@settings(max_examples=30, deadline=None)
@given(st.integers(3, 40), st.floats(0.1, 50.0), st.floats(-20.0, 20.0),
       st.integers(0, 2 ** 31 - 1))
def test_znorm_affine_invariant(n, a, b, seed):
    """z-norm kills scale/shift — the property that lets it fuse SPLADE
    and ColBERT scores 'of drastically different distributions'."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=n).astype(np.float32))
    mask = jnp.ones(n, bool)
    n1 = H.znorm(x, mask)
    n2 = H.znorm(a * x + b, mask)
    np.testing.assert_allclose(np.asarray(n1), np.asarray(n2), rtol=1e-2,
                               atol=1e-3)


def test_normalizers_respect_mask():
    x = jnp.asarray([1.0, 2.0, 3.0, 1e9])     # huge padded entry
    mask = jnp.asarray([True, True, True, False])
    for name, fn in H.NORMALIZERS.items():
        out = np.asarray(fn(x, mask))[:3]
        assert np.all(np.isfinite(out)), name
        assert np.abs(out).max() < 10, name    # padding did not leak


def test_hybrid_alpha_limits():
    s = jnp.asarray([3.0, 1.0, 2.0])
    c = jnp.asarray([1.0, 3.0, 2.0])
    mask = jnp.ones(3, bool)
    # α=0 → ColBERT (rerank) order; α=1 → SPLADE order
    h0 = np.asarray(H.hybrid_scores(s, c, mask, alpha=0.0))
    h1 = np.asarray(H.hybrid_scores(s, c, mask, alpha=1.0))
    assert np.argmax(h0) == 1
    assert np.argmax(h1) == 0


@settings(max_examples=20, deadline=None)
@given(st.floats(0.0, 1.0), st.integers(0, 2 ** 31 - 1))
def test_hybrid_padding_is_neg_inf(alpha, seed):
    rng = np.random.default_rng(seed)
    s = jnp.asarray(rng.normal(size=6).astype(np.float32))
    c = jnp.asarray(rng.normal(size=6).astype(np.float32))
    mask = jnp.asarray([True, True, False, True, False, True])
    out = np.asarray(H.hybrid_scores(s, c, mask, alpha=alpha))
    assert np.all(np.isinf(out[~np.asarray(mask)]))


# ---------------------------------------------------------------------------
# PLAID
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def sidx(small_corpus):
    return build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)


def _plaid(sidx, index, device_resident=False):
    """A retriever whose ``colbert`` method is PLAID at the test
    settings, served through its compiled stage plan."""
    return MultiStageRetriever(sidx, PLAIDSearcher(
        index, PlaidParams(nprobe=8, candidate_cap=512, ndocs=128, k=50),
        device_resident=device_resident))


@pytest.fixture(scope="module")
def searcher(built_index, sidx):
    index = ColBERTIndex(built_index, mode="mmap")
    return index, _plaid(sidx, index)


def brute_force(index: ColBERTIndex, q_emb):
    """Exact MaxSim over every decompressed doc in the index."""
    pids = np.arange(index.n_docs)
    c, r, v = index.gather_doc_tokens(pids)
    emb = decode_embeddings(jnp.asarray(r), jnp.asarray(c),
                            jnp.asarray(index.centroids),
                            jnp.asarray(index.bucket_weights), index.nbits)
    emb = emb * jnp.asarray(v)[..., None]
    scores = maxsim_scores_ref(jnp.asarray(q_emb), emb, jnp.asarray(v))
    return np.asarray(scores)


def _rerank(searcher: PLAIDSearcher, q_emb, pids):
    """Exact MaxSim for given candidates (the paper's Rerank path),
    through the residual gather and scoring the rerank plans run.
    pids: (C,) (−1 pad) → scores (C,) aligned with pids."""
    q, q_valid = pad_query_batch([q_emb])
    pids = np.asarray(pids)[None]
    codes, packed, valid = searcher.gather_tokens_batch(pids)
    return np.asarray(searcher.score_gathered_lazy(q, q_valid, codes,
                                                   packed, valid, pids))[0]


def test_plaid_agrees_with_brute_force(searcher, small_corpus):
    index, retr = searcher
    hits = 0
    for qi in range(20):
        q = small_corpus["q_embs"][qi]
        exact = brute_force(index, q)
        pids, scores = retr.search("colbert", q_emb=q, k=10)
        # top-1 of PLAID is within the true top-3 (approximation in
        # stages 1-3 can reorder near-ties)
        true_top3 = set(np.argsort(-exact)[:3].tolist())
        hits += int(pids[0]) in true_top3
    assert hits >= 18


def test_rerank_equals_exact_scoring(searcher, small_corpus):
    index, retr = searcher
    q = small_corpus["q_embs"][0]
    pids = np.arange(40)
    exact = brute_force(index, q)[:40]
    got = _rerank(retr.searcher, q, pids)
    np.testing.assert_allclose(got, exact, rtol=1e-3, atol=1e-3)


def test_rerank_respects_padding(searcher, small_corpus):
    _, retr = searcher
    q = small_corpus["q_embs"][1]
    pids = np.array([3, -1, 7, -1])
    out = _rerank(retr.searcher, q, pids)
    assert np.isinf(out[1]) and np.isinf(out[3])
    assert np.isfinite(out[0]) and np.isfinite(out[2])


def test_mmap_and_ram_modes_identical(built_index, small_corpus, sidx):
    res = {}
    for mode in ("ram", "mmap"):
        retr = _plaid(sidx, ColBERTIndex(built_index, mode=mode))
        res[mode] = retr.search("colbert", q_emb=small_corpus["q_embs"][2],
                                k=20)
    np.testing.assert_array_equal(res["ram"][0], res["mmap"][0])
    np.testing.assert_allclose(res["ram"][1], res["mmap"][1], rtol=1e-6)


def test_device_resident_matches_host_path(built_index, small_corpus, sidx):
    index = ColBERTIndex(built_index, mode="ram")
    host = _plaid(sidx, index)
    dev = _plaid(sidx, index, device_resident=True)
    q = small_corpus["q_embs"][3]
    p1, s1 = host.search("colbert", q_emb=q, k=20)
    p2, s2 = dev.search("colbert", q_emb=q, k=20)
    np.testing.assert_array_equal(p1, p2)
    np.testing.assert_allclose(s1, s2, rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# multi-stage: the paper's access-minimisation claim
# ---------------------------------------------------------------------------

def test_rerank_touches_fewer_pages_than_full_plaid(built_index,
                                                    small_corpus):
    """The paper's regime: PLAID's candidate union is larger than
    SPLADE's ``first_k``, so PLAID reads codes for the whole union and
    residuals for its survivors while rerank reads ``first_k`` rows. On
    this corpus that takes ``nprobe`` 16 (a union of ~100 passages a
    query; at 8 it is ~44, below ``first_k`` 50). Padded candidate
    slots are never read, so only real rows count."""
    index = ColBERTIndex(built_index, mode="mmap")
    s = PLAIDSearcher(index, PlaidParams(nprobe=16, candidate_cap=512,
                                         ndocs=256, k=20))
    sidx = build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)
    retr = MultiStageRetriever(sidx, s, MultiStageParams(first_k=50, k=20))

    index.store.stats.reset()
    for qi in range(10):
        retr.search("colbert", q_emb=small_corpus["q_embs"][qi])
    full_tokens = index.store.stats.tokens_read
    union_tokens = full_tokens - index.store.stats.residual_tokens_read
    assert union_tokens > 10 * retr.params.first_k * index.doc_maxlen

    index.store.stats.reset()
    for qi in range(10):
        retr.search("rerank", q_emb=small_corpus["q_embs"][qi],
                    term_ids=small_corpus["q_term_ids"][qi],
                    term_weights=small_corpus["q_term_weights"][qi])
    rerank_tokens = index.store.stats.tokens_read
    # SPLADE top-50 rerank reads far less of the pool than full PLAID
    assert rerank_tokens < 0.5 * full_tokens


def test_quality_ordering_matches_paper(built_index, small_corpus):
    """Table 2's relationships on the controlled corpus: Hybrid beats
    Rerank and SPLADE; Rerank ≈ ColBERT; SPLADE is the weakest."""
    index = ColBERTIndex(built_index, mode="mmap")
    s = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                         ndocs=256, k=50))
    sidx = build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)
    retr = MultiStageRetriever(sidx, s,
                               MultiStageParams(first_k=100, k=50,
                                                alpha=0.3))
    ranked = {m: [] for m in ("colbert", "splade", "rerank", "hybrid")}
    n_q = 40
    for qi in range(n_q):
        for m in ranked:
            pids, _ = retr.search(
                m, q_emb=small_corpus["q_embs"][qi],
                term_ids=small_corpus["q_term_ids"][qi],
                term_weights=small_corpus["q_term_weights"][qi])
            ranked[m].append(pids)
    qrels = small_corpus["qrels"][:n_q]
    mrr = {m: metrics.mrr_at_k(np.stack(v), qrels, 10)
           for m, v in ranked.items()}
    assert mrr["hybrid"] >= mrr["rerank"] - 1e-9
    assert mrr["hybrid"] > mrr["splade"]
    assert mrr["rerank"] >= 0.9 * mrr["colbert"]
    assert mrr["colbert"] > mrr["splade"]
