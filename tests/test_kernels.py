"""Per-kernel validation: interpret-mode Pallas body vs pure-jnp oracle
across shape/dtype sweeps, plus hypothesis property tests on the
oracles themselves."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.index.residual import unpack_codes
from repro.kernels.decompress_maxsim.decompress_maxsim import (
    HIGHEST,
    LANES,
    NEG,
    kernel_operands,
    score_atol,
)
from repro.kernels.decompress_maxsim.ops import decompress_maxsim_scores
from repro.kernels.maxsim.ops import maxsim_scores
from repro.kernels.maxsim.ref import maxsim_scores_ref
from repro.kernels.splade_score.ops import (splade_block_scores,
                                            splade_block_scores_batch,
                                            splade_block_topk_batch)


# ---------------------------------------------------------------------------
# maxsim
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("C,Ld,Lq,d,block_c", [
    (16, 24, 32, 128, 16),
    (20, 8, 8, 64, 8),        # C not multiple of block (pads)
    (1, 180, 32, 128, 16),    # single candidate
    (64, 17, 5, 32, 32),      # odd doc length
])
def test_maxsim_interpret_matches_ref(C, Ld, Lq, d, block_c):
    k = jax.random.PRNGKey(C * 101 + Ld)
    q = jax.random.normal(k, (Lq, d), jnp.float32)
    docs = jax.random.normal(jax.random.fold_in(k, 1), (C, Ld, d))
    valid = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.8, (C, Ld))
    qv = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.9, (Lq,))
    a = maxsim_scores(q, docs, valid, qv, impl="interpret", block_c=block_c)
    b = maxsim_scores(q, docs, valid, qv, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxsim_dtypes(dtype):
    k = jax.random.PRNGKey(7)
    q = jax.random.normal(k, (8, 64), dtype)
    docs = jax.random.normal(jax.random.fold_in(k, 1), (16, 12, 64), dtype)
    valid = jnp.ones((16, 12), bool)
    a = maxsim_scores(q, docs, valid, impl="interpret")
    b = maxsim_scores(q, docs, valid, impl="ref")
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=tol,
                               atol=tol)


def test_maxsim_all_invalid_doc_scores_zero():
    q = jnp.ones((4, 16))
    docs = jnp.ones((3, 5, 16))
    valid = jnp.array([[True] * 5, [False] * 5, [True] * 5])
    s = maxsim_scores(q, docs, valid, impl="ref")
    assert float(s[1]) == 0.0


@settings(max_examples=25, deadline=None)
@given(st.integers(2, 10), st.integers(2, 12), st.integers(2, 8),
       st.integers(0, 2 ** 31 - 1))
def test_maxsim_doc_token_permutation_invariant(C, Ld, Lq, seed):
    """MaxSim is a max over doc tokens — permuting them is a no-op."""
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (Lq, 16))
    docs = jax.random.normal(jax.random.fold_in(k, 1), (C, Ld, 16))
    valid = jax.random.bernoulli(jax.random.fold_in(k, 2), 0.7, (C, Ld))
    perm = jax.random.permutation(jax.random.fold_in(k, 3), Ld)
    a = maxsim_scores_ref(q, docs, valid)
    b = maxsim_scores_ref(q, docs[:, perm], valid[:, perm])
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                               atol=1e-5)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 6), st.integers(1, 8), st.integers(0, 2 ** 31 - 1))
def test_maxsim_padding_tokens_never_change_scores(C, Ld, seed):
    """Appending invalid tokens must not move any score."""
    k = jax.random.PRNGKey(seed)
    q = jax.random.normal(k, (4, 8))
    docs = jax.random.normal(jax.random.fold_in(k, 1), (C, Ld, 8))
    valid = jnp.ones((C, Ld), bool)
    pad = 100.0 * jax.random.normal(jax.random.fold_in(k, 2), (C, 3, 8))
    docs2 = jnp.concatenate([docs, pad], axis=1)
    valid2 = jnp.concatenate([valid, jnp.zeros((C, 3), bool)], axis=1)
    np.testing.assert_allclose(np.asarray(maxsim_scores_ref(q, docs, valid)),
                               np.asarray(maxsim_scores_ref(q, docs2, valid2)),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# decompress_maxsim (fused)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nbits,C,Ld,K", [
    (4, 16, 24, 64),
    (4, 130, 7, 64),          # C spans two lane tiles; odd doc length
    (2, 8, 12, 32),
    (2, 24, 8, 16),
])
def test_decompress_maxsim_interpret_matches_ref(nbits, C, Ld, K):
    d = 64
    k = jax.random.PRNGKey(nbits * 7 + C)
    q = jax.random.normal(k, (16, d))
    packed = jax.random.randint(jax.random.fold_in(k, 1),
                                (C, Ld, d * nbits // 8), 0, 256, jnp.int32
                                ).astype(jnp.uint8)
    cids = jax.random.randint(jax.random.fold_in(k, 2), (C, Ld), 0, K)
    valid = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.85, (C, Ld))
    cent = jax.random.normal(jax.random.fold_in(k, 4), (K, d))
    bw = jnp.linspace(-0.3, 0.3, 2 ** nbits)
    a = decompress_maxsim_scores(q, packed, cids, valid, cent, bw,
                                 nbits=nbits, impl="interpret")
    b = np.asarray(decompress_maxsim_scores(q, packed, cids, valid, cent,
                                            bw, nbits=nbits, impl="ref"))
    # the kernel scores q·c + q·r, the reference q·(c + r): the same
    # float32 terms summed in another order (score_atol's docstring)
    np.testing.assert_allclose(np.asarray(a), b, rtol=0,
                               atol=score_atol(b))


def test_fused_equals_decompress_then_maxsim():
    """The fusion is exact: same numbers as the two-step pipeline."""
    nbits, C, Ld, K, d = 4, 12, 10, 32, 64
    k = jax.random.PRNGKey(3)
    q = jax.random.normal(k, (8, d))
    packed = jax.random.randint(jax.random.fold_in(k, 1),
                                (C, Ld, d // 2), 0, 256).astype(jnp.uint8)
    cids = jax.random.randint(jax.random.fold_in(k, 2), (C, Ld), 0, K)
    valid = jnp.ones((C, Ld), bool)
    cent = jax.random.normal(jax.random.fold_in(k, 4), (K, d))
    bw = jnp.linspace(-0.2, 0.2, 16)
    codes = unpack_codes(packed, nbits)
    emb = cent[cids] + bw[codes.astype(jnp.int32)]
    two_step = maxsim_scores(q, emb, valid, impl="ref")
    fused = decompress_maxsim_scores(q, packed, cids, valid, cent, bw,
                                     nbits=nbits, impl="ref")
    np.testing.assert_allclose(np.asarray(fused), np.asarray(two_step),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B", [1, 3])
@pytest.mark.parametrize("nbits", [2, 4])
def test_kernel_operands_equal_elementwise_formulation(B, nbits):
    """The kernels' operands, element by element: ``qc[b, t, l, i, j]``
    is the centroid score ``(q_i · C)[cids[b, t·LANES + j, l]]`` of a
    valid token and ``NEG`` elsewhere; ``packed_t`` puts candidates on
    lanes; ``q_perm`` puts dim ``j·cpb + s`` in column ``s·pd + j``.
    Bitwise: the kernel must see the same numbers however they are
    gathered."""
    C, Ld, Lq, d, K = 200, 37, 8, 64, 48
    rng = np.random.default_rng(B * 10 + nbits)
    pd, cpb = d * nbits // 8, 8 // nbits
    q = rng.standard_normal((B, Lq, d)).astype(np.float32)
    packed = rng.integers(0, 256, (B, C, Ld, pd)).astype(np.uint8)
    cids = rng.integers(0, K, (B, C, Ld)).astype(np.int32)
    valid = rng.random((B, C, Ld)) < 0.7
    q_valid = rng.random((B, Lq)) < 0.75
    q_valid[:, 0] = True
    cent = rng.standard_normal((K, d)).astype(np.float32)
    q_perm, packed_t, qc = (np.asarray(x) for x in kernel_operands(
        q, packed, cids, valid, q_valid, cent, nbits))

    qz = q * q_valid[..., None]
    table = np.asarray(jnp.einsum("bqd,kd->bqk", qz, cent,
                                  precision=HIGHEST,
                                  preferred_element_type=jnp.float32))
    T = -(-C // LANES)
    want_qc = np.full((B, T, Ld, Lq, LANES), NEG, np.float32)
    want_packed = np.zeros((B, T, Ld, pd, LANES), np.uint8)
    for b, c, t in np.ndindex(B, C, Ld):
        if valid[b, c, t]:
            for i in range(Lq):
                want_qc[b, c // LANES, t, i, c % LANES] = \
                    table[b, i, cids[b, c, t]]
        for j in range(pd):
            want_packed[b, c // LANES, t, j, c % LANES] = packed[b, c, t, j]
    want_q = np.zeros((B, Lq, d), np.float32)
    for s in range(cpb):
        for j in range(pd):
            want_q[:, :, s * pd + j] = qz[:, :, j * cpb + s]
    assert qc.shape == want_qc.shape and qc.dtype == np.float32
    assert np.array_equal(qc, want_qc)
    assert np.array_equal(packed_t, want_packed)
    assert np.array_equal(q_perm, want_q)


# ---------------------------------------------------------------------------
# splade_score
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("Qt,max_df,n_docs,block_d,chunk", [
    (8, 128, 500, 256, 128),
    (4, 64, 1000, 512, 256),
    (16, 32, 300, 128, 512),   # E not multiple of chunk (pads)
])
def test_splade_interpret_matches_ref(Qt, max_df, n_docs, block_d, chunk):
    k = jax.random.PRNGKey(Qt + max_df)
    pids = jax.random.randint(k, (Qt, max_df), -1, n_docs, jnp.int32)
    imps = jax.random.uniform(jax.random.fold_in(k, 1), (Qt, max_df))
    w = jax.random.uniform(jax.random.fold_in(k, 2), (Qt,))
    a = splade_block_scores(pids, imps, w, n_docs=n_docs,
                            impl="interpret", block_d=block_d, chunk=chunk)
    b = splade_block_scores(pids, imps, w, n_docs=n_docs, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


@pytest.mark.parametrize("B,Qt,max_df,n_docs,block_d,chunk", [
    (3, 8, 64, 500, 256, 128),
    (1, 4, 32, 300, 128, 256),    # B=1 degenerate; E pads to chunk
    (5, 16, 16, 700, 512, 64),
])
def test_splade_batch_interpret_matches_ref(B, Qt, max_df, n_docs,
                                            block_d, chunk):
    k = jax.random.PRNGKey(B * 31 + Qt)
    pids = jax.random.randint(k, (B, Qt, max_df), -1, n_docs, jnp.int32)
    imps = jax.random.uniform(jax.random.fold_in(k, 1), (B, Qt, max_df))
    w = jax.random.uniform(jax.random.fold_in(k, 2), (B, Qt))
    a = splade_block_scores_batch(pids, imps, w, n_docs=n_docs,
                                  impl="interpret", block_d=block_d,
                                  chunk=chunk)
    b = splade_block_scores_batch(pids, imps, w, n_docs=n_docs, impl="ref")
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                               atol=1e-5)


def test_splade_batch_ref_equals_per_query_loop():
    B, Qt, max_df, n_docs = 4, 6, 48, 400
    k = jax.random.PRNGKey(9)
    pids = jax.random.randint(k, (B, Qt, max_df), -1, n_docs, jnp.int32)
    imps = jax.random.uniform(jax.random.fold_in(k, 1), (B, Qt, max_df))
    w = jax.random.uniform(jax.random.fold_in(k, 2), (B, Qt))
    batch = splade_block_scores_batch(pids, imps, w, n_docs=n_docs,
                                      impl="ref")
    loop = jnp.stack([splade_block_scores(pids[b], imps[b], w[b],
                                          n_docs=n_docs, impl="ref")
                      for b in range(B)])
    np.testing.assert_allclose(np.asarray(batch), np.asarray(loop),
                               rtol=1e-5, atol=1e-6)


def test_splade_fused_topk_matches_scores_then_topk():
    B, Qt, max_df, n_docs, k_top = 3, 5, 40, 250, 17
    k = jax.random.PRNGKey(21)
    pids = jax.random.randint(k, (B, Qt, max_df), -1, n_docs, jnp.int32)
    imps = jax.random.uniform(jax.random.fold_in(k, 1), (B, Qt, max_df))
    w = jax.random.uniform(jax.random.fold_in(k, 2), (B, Qt))
    top_pids, top_scores = splade_block_topk_batch(pids, imps, w,
                                                   n_docs=n_docs, k=k_top,
                                                   impl="ref")
    scores = np.asarray(splade_block_scores_batch(pids, imps, w,
                                                  n_docs=n_docs, impl="ref"))
    for b in range(B):
        want = np.sort(scores[b])[::-1][:k_top]
        np.testing.assert_allclose(np.asarray(top_scores[b]), want,
                                   rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(scores[b][np.asarray(top_pids[b])],
                                   np.asarray(top_scores[b]), rtol=1e-6)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 6), st.integers(1, 32), st.integers(0, 2 ** 31 - 1))
def test_splade_ref_is_exact_posting_sum(Qt, max_df, seed):
    """Oracle equals a literal python loop over postings."""
    rng = np.random.default_rng(seed)
    n_docs = 50
    pids = rng.integers(-1, n_docs, (Qt, max_df)).astype(np.int32)
    imps = rng.random((Qt, max_df)).astype(np.float32)
    w = rng.random(Qt).astype(np.float32)
    expected = np.zeros(n_docs, np.float32)
    for t in range(Qt):
        for j in range(max_df):
            if pids[t, j] >= 0:
                expected[pids[t, j]] += w[t] * imps[t, j]
    got = np.asarray(splade_block_scores(
        jnp.asarray(pids), jnp.asarray(imps), jnp.asarray(w),
        n_docs=n_docs, impl="ref"))
    np.testing.assert_allclose(got, expected, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# fused_rerank (decompress + MaxSim + top-k in one dispatch)
# ---------------------------------------------------------------------------

from repro.kernels.decompress_maxsim.ops import decompress_maxsim_scores_batch
from repro.kernels.fused_rerank.ops import (fused_rerank_topk,
                                            fused_rerank_topk_batch)


def _rerank_case(seed, C, Ld, nbits, K=32, d=64, Lq=8, B=None,
                 mask_p=0.85):
    """Random compressed candidate set (+ optional leading batch dim)."""
    k = jax.random.PRNGKey(seed)
    lead = () if B is None else (B,)
    q = jax.random.normal(k, lead + (Lq, d), jnp.float32)
    packed = jax.random.randint(jax.random.fold_in(k, 1),
                                lead + (C, Ld, d * nbits // 8), 0, 256,
                                jnp.int32).astype(jnp.uint8)
    cids = jax.random.randint(jax.random.fold_in(k, 2), lead + (C, Ld),
                              0, K, jnp.int32)
    valid = jax.random.bernoulli(jax.random.fold_in(k, 3), 0.8,
                                 lead + (C, Ld))
    cmask = jax.random.bernoulli(jax.random.fold_in(k, 4), mask_p,
                                 lead + (C,))
    qv = jax.random.bernoulli(jax.random.fold_in(k, 5), 0.9, lead + (Lq,))
    cent = jax.random.normal(jax.random.fold_in(k, 6), (K, d), jnp.float32)
    bw = jnp.linspace(-0.3, 0.3, 2 ** nbits, dtype=jnp.float32)
    return q, packed, cids, valid, cmask, cent, bw, qv


def _masked_ref_scores(q, packed, cids, valid, cmask, cent, bw, qv, nbits):
    """Reference MaxSim scores, -inf at masked candidates (leading B)."""
    scores = np.asarray(decompress_maxsim_scores_batch(
        q, packed, cids, valid, cent, bw, nbits=nbits, q_valid=qv,
        impl="ref"))
    return np.where(np.asarray(cmask), scores, -np.inf)


def _assert_topk_close(vals, idx, ref_vals, ref_idx, full_ref):
    """Kernel top-k vs reference top-k (rows of a batch).

    Scores agree within ``score_atol`` (both sides compute full-float32
    dots; only the summation order differs). Indices must be equal
    wherever the reference score is farther than that from both
    neighbours; inside a near-tie either order is right, so there each
    returned index must carry its own reference score."""
    vals, idx = np.atleast_2d(vals), np.atleast_2d(idx)
    ref_vals, ref_idx = np.atleast_2d(ref_vals), np.atleast_2d(ref_idx)
    full_ref = np.atleast_2d(full_ref)
    tol = score_atol(full_ref)
    np.testing.assert_array_equal(np.isfinite(vals), np.isfinite(ref_vals))
    fin = np.isfinite(ref_vals)
    np.testing.assert_allclose(vals[fin], ref_vals[fin], rtol=0, atol=tol)
    gap = np.abs(np.diff(np.where(fin, ref_vals, -1e30), axis=1))
    far = np.ones(ref_vals.shape, bool)
    far[:, 1:] &= gap > tol
    far[:, :-1] &= gap > tol
    np.testing.assert_array_equal(idx[far], ref_idx[far])
    np.testing.assert_array_equal(idx < 0, ref_idx < 0)
    for b in range(idx.shape[0]):
        real = idx[b] >= 0
        np.testing.assert_allclose(full_ref[b][idx[b][real]],
                                   vals[b][real], rtol=0, atol=tol)


@pytest.mark.parametrize("nbits,C,Ld,k_top", [
    (4, 32, 12, 10),
    (4, 33, 12, 10),         # ragged C (pads to the lane tile)
    (2, 16, 1, 16),          # single-token docs, k == C
    (4, 24, 6, 40),          # k > C (pads tail with (-inf, -1))
    (2, 8, 5, 1),            # k == 1
    (4, 300, 3, 150),        # three lane tiles, two-row running state
])
def test_fused_rerank_interpret_bitwise_matches_ref(nbits, C, Ld, k_top):
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        nbits * 101 + C, C, Ld, nbits)
    a = fused_rerank_topk(q, packed, cids, valid, cmask, cent, bw,
                          nbits=nbits, k=k_top, q_valid=qv,
                          impl="interpret")
    b = fused_rerank_topk(q, packed, cids, valid, cmask, cent, bw,
                          nbits=nbits, k=k_top, q_valid=qv, impl="ref")
    full = _masked_ref_scores(q[None], packed[None], cids[None],
                              valid[None], cmask[None], cent, bw, qv[None],
                              nbits)
    _assert_topk_close(np.asarray(a[0]), np.asarray(a[1]),
                       np.asarray(b[0]), np.asarray(b[1]), full)


@pytest.mark.parametrize("nbits,B,C,Ld,k_top", [
    (4, 3, 32, 10, 12),
    (2, 1, 24, 4, 24),        # B=1 degenerate
    (4, 5, 40, 8, 64),        # k > C
])
def test_fused_rerank_batch_interpret_bitwise_matches_ref(nbits, B, C, Ld,
                                                          k_top):
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        nbits * 7 + B, C, Ld, nbits, B=B)
    a = fused_rerank_topk_batch(q, packed, cids, valid, cmask, cent, bw,
                                nbits=nbits, k=k_top, q_valid=qv,
                                impl="interpret")
    b = fused_rerank_topk_batch(q, packed, cids, valid, cmask, cent, bw,
                                nbits=nbits, k=k_top, q_valid=qv,
                                impl="ref")
    full = _masked_ref_scores(q, packed, cids, valid, cmask, cent, bw, qv,
                              nbits)
    _assert_topk_close(np.asarray(a[0]), np.asarray(a[1]),
                       np.asarray(b[0]), np.asarray(b[1]), full)


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_fused_rerank_bitwise_matches_split_pipeline(impl):
    """The fused tail == split dispatches + stable host argsort, ties
    broken toward the lower candidate index: bitwise for the reference,
    which runs the split path's own code, and within ``score_atol`` for
    the kernel, which sums the same float32 terms in another order."""
    nbits, B, C, Ld, k_top = 4, 4, 32, 8, 12
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        17, C, Ld, nbits, B=B)
    final = _masked_ref_scores(q, packed, cids, valid, cmask, cent, bw, qv,
                               nbits)
    order = np.argsort(-final, axis=1, kind="stable")[:, :k_top]
    want = np.take_along_axis(final, order, axis=1).astype(np.float32)
    vals, idx = fused_rerank_topk_batch(
        q, packed, cids, valid, cmask, cent, bw, nbits=nbits, k=k_top,
        q_valid=qv, impl=impl)
    if impl == "ref":
        np.testing.assert_array_equal(np.asarray(idx),
                                      order.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(vals), want)
    else:
        _assert_topk_close(np.asarray(vals), np.asarray(idx), want,
                           order.astype(np.int32), final)


def test_fused_rerank_duplicate_scores_break_ties_by_index():
    """Identical candidates produce identical scores — selection must
    order them by ascending candidate index (lax.top_k semantics)."""
    nbits, C, Ld, k_top = 4, 16, 6, 8
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        5, C, Ld, nbits, mask_p=1.0)
    # every candidate is a copy of candidate 0 → C-way score tie
    packed = jnp.broadcast_to(packed[:1], packed.shape)
    cids = jnp.broadcast_to(cids[:1], cids.shape)
    valid = jnp.broadcast_to(valid[:1], valid.shape)
    for impl in ("ref", "interpret"):
        _, idx = fused_rerank_topk(q, packed, cids, valid, cmask, cent,
                                   bw, nbits=nbits, k=k_top, q_valid=qv,
                                   impl=impl)
        np.testing.assert_array_equal(np.asarray(idx),
                                      np.arange(k_top, dtype=np.int32))


def test_fused_rerank_all_masked_and_empty_edges():
    nbits, C, Ld, k_top = 2, 16, 4, 6
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        11, C, Ld, nbits)
    # all-masked candidate row: every score -inf, indices still the
    # stable prefix (lax.top_k returns ascending indices on full ties)
    none = jnp.zeros_like(cmask)
    for impl in ("ref", "interpret"):
        vals, idx = fused_rerank_topk(q, packed, cids, valid, none, cent,
                                      bw, nbits=nbits, k=k_top,
                                      q_valid=qv, impl=impl)
        assert np.all(np.asarray(vals) == -np.inf)
        np.testing.assert_array_equal(np.asarray(idx),
                                      np.arange(k_top, dtype=np.int32))
    # empty candidate set: fully padded output
    vals, idx = fused_rerank_topk(q, packed[:0], cids[:0], valid[:0],
                                  cmask[:0], cent, bw, nbits=nbits,
                                  k=k_top, impl="ref")
    assert np.all(np.asarray(vals) == -np.inf)
    assert np.all(np.asarray(idx) == -1)


@settings(max_examples=20, deadline=None)
@given(st.integers(1, 24), st.integers(1, 6), st.integers(1, 30),
       st.integers(0, 2 ** 31 - 1))
def test_fused_rerank_topk_roundtrip_property(C, Ld, k_top, seed):
    """Returned (score, index) pairs must be exactly the k best masked
    scores in (desc, index-asc) order, and indices must map back to the
    scores the split pipeline computes for them."""
    nbits = 4
    q, packed, cids, valid, cmask, cent, bw, qv = _rerank_case(
        seed, C, Ld, nbits, mask_p=0.7)
    vals, idx = fused_rerank_topk(q, packed, cids, valid, cmask, cent,
                                  bw, nbits=nbits, k=k_top, q_valid=qv,
                                  impl="ref")
    vals, idx = np.asarray(vals), np.asarray(idx)
    scores = np.asarray(decompress_maxsim_scores_batch(
        q[None], packed[None], cids[None], valid[None], cent, bw,
        nbits=nbits, q_valid=qv[None], impl="ref"))[0]
    final = np.where(np.asarray(cmask), scores, -np.inf)
    kk = min(k_top, C)
    order = np.argsort(-final, kind="stable")[:kk]
    np.testing.assert_array_equal(idx[:kk], order.astype(np.int32))
    np.testing.assert_array_equal(vals[:kk],
                                  final[order].astype(np.float32))
    assert np.all(vals[kk:] == -np.inf) and np.all(idx[kk:] == -1)
