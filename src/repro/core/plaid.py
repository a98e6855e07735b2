"""PLAID multi-stage search over the compressed ColBERTv2 index.

Stages (Santhanam et al., CIKM'22):
  1. centroid scoring:   S_c = Q · C^T, top-``nprobe`` centroids/q-token
  2. candidate generation from the IVF
  3. approximate scoring by centroid interaction (codes only — cheap,
     *no residual access*)
  4. residual decompression + exact MaxSim for the surviving ``ndocs``

The class provides the batched stages — jitted device steps and host
gathers through the PagedStore (mmap tier), mirroring the paper's
Python↔C++ split — that ``MultiStageRetriever.compile_plan`` composes
into each method's stage plan; a query runs as a batch of one.
``device_resident=True`` instead keeps the whole pool in device memory.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.common.utils import next_pow2 as _next_pow2
from repro.core import hybrid as hybrid_mod
from repro.index.builder import ColBERTIndex
from repro.kernels.decompress_maxsim.ops import decompress_maxsim_scores_batch
from repro.kernels.fused_rerank.ops import fused_rerank_topk_batch


@dataclasses.dataclass(frozen=True)
class PlaidParams:
    nprobe: int = 4
    candidate_cap: int = 4096    # max candidate pids after stage 2
    ndocs: int = 256             # survivors entering exact scoring
    k: int = 100                 # final results


# --------------------------------------------------------------------------
# jitted stage kernels (shapes static per index)
# --------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("cap",))
def stage2_candidates(ivf_padded, cids, cap: int):
    """ivf_padded (K, P) int32 (−1 fill); cids (Lq, nprobe) →
    unique candidate pids (cap,) (−1 fill)."""
    cand = ivf_padded[cids.reshape(-1)].reshape(-1)      # (Lq*nprobe*P,)
    # unique with static size; -1 fill sorts first so drop via where
    uniq = jnp.unique(cand, size=cap + 1, fill_value=-1)
    uniq = jnp.where(uniq >= 0, uniq, -1)
    # compact: move -1s to the back (sort by (is_pad, value))
    order = jnp.argsort(jnp.where(uniq >= 0, 0, 1), stable=True)
    return uniq[order][:cap]


@jax.jit
def stage3_approx_score(scores_c, cand_codes, cand_valid, q_valid=None):
    """Centroid-interaction approximation.

    scores_c: (Lq, K); cand_codes: (C, Ld) int32 centroid ids;
    cand_valid: (C, Ld) → approx scores (C,)."""
    s = scores_c[:, cand_codes]                  # (Lq, C, Ld)
    s = jnp.where(cand_valid[None], s, -1e30)
    per_q = jnp.max(s, axis=-1)                  # (Lq, C)
    per_q = jnp.where(per_q <= -1e29, 0.0, per_q)
    if q_valid is not None:
        per_q = per_q * q_valid[:, None]
    return jnp.sum(per_q, axis=0)                # (C,)


@functools.partial(jax.jit, static_argnames=("nbits", "k", "b",
                                             "normalizer", "impl"))
def fused_hybrid_tail(q, packed, cids, valid, cand_mask, centroids,
                      bucket_weights, q_valid, s_scores, alphas, *,
                      nbits: int, k: int, b: int, normalizer: str,
                      impl: str = "auto"):
    """Fused stage-4 tail for the hybrid method: decompress + MaxSim
    (the fused scoring kernel on TPU), α-interpolated z-normed fusion
    with the stage-1 scores, and the per-query top-k — ONE dispatch.

    Hybrid cannot take the top-k-only ``fused_rerank`` kernel end-to-end
    because the normaliser needs per-query statistics over the *full*
    candidate list; the (b, C) exact-score tensor is tiny (C = first_k),
    so the win here is folding masking + fusion + selection into the
    scoring dispatch — no host argsort, no intermediate syncs. Scoring
    runs on the padded ``Bp`` rows and slices to the ``b`` real ones
    exactly like the split path, so results stay bitwise-identical.
    """
    c = decompress_maxsim_scores_batch(
        q, packed, cids, valid, centroids, bucket_weights, nbits=nbits,
        q_valid=q_valid, impl=impl)
    c = jnp.where(cand_mask, c, -jnp.inf)[:b]
    final = hybrid_mod.hybrid_scores(s_scores, c, cand_mask[:b],
                                     alpha=alphas, normalizer=normalizer)
    return jax.lax.top_k(final, k)


# --------------------------------------------------------------------------
# batched stage kernels (cross-query micro-batches)
# --------------------------------------------------------------------------

def pad_query_batch_host(q_embs, lq_multiple: int = 4):
    """Numpy-only variant of :func:`pad_query_batch` (no device
    transfer) — for host-bound pipeline stages, which must not touch
    the device client while a device stage is dispatching."""
    arrs = [np.asarray(qe, np.float32) for qe in q_embs]
    d = arrs[0].shape[-1]
    lq_pad = -(-max(a.shape[0] for a in arrs) // lq_multiple) * lq_multiple
    q = np.zeros((len(arrs), lq_pad, d), np.float32)
    valid = np.zeros((len(arrs), lq_pad), bool)
    for i, a in enumerate(arrs):
        q[i, :a.shape[0]] = a
        valid[i, :a.shape[0]] = True
    return q, valid


def pad_query_batch(q_embs, lq_multiple: int = 4):
    """Stack ragged queries. q_embs: sequence of (Lq_i, d) arrays or an
    already-stacked (B, Lq, d) array → ((B, Lq_pad, d) f32 zero-padded,
    (B, Lq_pad) bool validity).

    ``Lq_pad`` rounds the longest query up to ``lq_multiple`` so ragged
    batches reuse a small set of compiled shapes instead of recompiling
    the batched stages per distinct length."""
    q, valid = pad_query_batch_host(q_embs, lq_multiple)
    return jnp.asarray(q), jnp.asarray(valid)


def _pad_batch_rows(q, q_valid, *extra):
    """Pad the batch dim to the next power of two by replicating the
    last real row (of ``q``/``q_valid`` and each array in ``extra``), so
    compiled batched stages are reused across nearby batch sizes and the
    padding rows add no new pids to the deduplicated host gathers.
    Returns (B_real, q, q_valid, *extra)."""
    B = q.shape[0]
    Bp = _next_pow2(B)
    if Bp == B:
        return (B, q, q_valid) + extra
    reps = Bp - B

    def pad(x):
        if isinstance(x, np.ndarray):
            return np.concatenate([x, np.repeat(x[-1:], reps, axis=0)],
                                  axis=0)
        return jnp.concatenate([x, jnp.repeat(x[-1:], reps, axis=0)],
                               axis=0)

    return (B, pad(q), pad(q_valid)) + tuple(pad(x) for x in extra)


@functools.partial(jax.jit, static_argnames=("nprobe",))
def stage1_centroid_probe_batch(q_emb, q_valid, centroids, nprobe: int):
    """q_emb (B, Lq, d), q_valid (B, Lq), centroids (K, d) →
    (scores_c (B, Lq, K), cids (B, Lq, nprobe))."""
    s = jnp.einsum("bqd,kd->bqk", q_emb, centroids,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)
    _, cids = jax.lax.top_k(s, nprobe)
    # padded query tokens must not widen the candidate set: replicate the
    # first (always-real) token's probes, which add nothing new
    cids = jnp.where(q_valid[..., None], cids, cids[:, :1, :])
    return s, cids.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("cap",))
def stage2_candidates_batch(ivf_padded, cids, cap: int):
    """cids (B, Lq, nprobe) → per-query unique candidates (B, cap)."""
    return jax.vmap(lambda c: stage2_candidates(ivf_padded, c, cap))(cids)


@jax.jit
def stage3_approx_score_batch(scores_c, cand_codes, cand_valid, q_valid):
    """Batched centroid-interaction approximation: scores_c (B, Lq, K),
    cand_codes/cand_valid (B, C, Ld), q_valid (B, Lq) → (B, C)."""
    return jax.vmap(stage3_approx_score)(scores_c, cand_codes, cand_valid,
                                         q_valid.astype(jnp.float32))


# --------------------------------------------------------------------------
# Orchestrator
# --------------------------------------------------------------------------

class PLAIDSearcher:
    def __init__(self, index: ColBERTIndex, params: PlaidParams = PlaidParams(),
                 device_resident: bool = False, ivf_pad: Optional[int] = None,
                 device=None):
        """``device`` (optional jax.Device) pins the searcher's device
        arrays; its dispatches then run there, since host inputs are
        uncommitted and follow them. A shard group gives shard i mesh
        device i (``launch.mesh.shard_device_map``)."""
        self.index = index
        self.params = params
        self.device = device
        put = (jnp.asarray if device is None
               else functools.partial(jax.device_put, device=device))
        self.centroids = put(index.centroids)
        self.bucket_weights = put(index.bucket_weights)
        self.ivf_padded = put(index.ivf.as_padded(ivf_pad))
        self.device_resident = device_resident
        if device_resident:
            # whole pool in device memory (the in-memory ColBERTv2 baseline
            # or the TPU serve path with the pool sharded over 'model')
            self.dev_codes = put(np.asarray(index.store.codes))
            self.dev_residuals = put(np.asarray(index.store.residuals))
            self.dev_offsets = put(index.doc_offsets)
            self.dev_doclens = put(index.doclens)

    # -- batched stage pieces ----------------------------------------------
    #
    # ``MultiStageRetriever.compile_plan`` wraps these into typed stages
    # (plaid_probe / host_gather / device_score / fuse_topk); the
    # synchronous and pipelined paths both run that plan.

    def probe_batch(self, q_embs) -> dict:
        """Stages 1-2 (device): pad/stack ragged queries, probe
        centroids, generate per-query unique candidate sets."""
        p = self.params
        q, q_valid = pad_query_batch(q_embs)
        B, q, q_valid = _pad_batch_rows(q, q_valid)
        scores_c, cids = stage1_centroid_probe_batch(q, q_valid,
                                                     self.centroids, p.nprobe)
        cand = stage2_candidates_batch(self.ivf_padded, cids,
                                       p.candidate_cap)       # (Bp, cap)
        return {"B": B, "q": q, "q_valid": q_valid,
                "scores_c": scores_c, "cand": cand}

    def gather_codes_batch(self, cand):
        """Codes-only candidate gather for the approximate stage — the
        host-bound step in mmap mode (never faults a residual page)."""
        if self.device_resident:
            codes, _, valid = self._gather_device_batch(cand)
            return codes, valid
        codes_np, _, valid_np = self._dedup_gather(np.asarray(cand),
                                                   codes_only=True)
        return jnp.asarray(codes_np), jnp.asarray(valid_np)

    def approx_select_batch(self, scores_c, codes, valid, q_valid, cand):
        """Stage 3 (device): centroid-interaction scores → the ``ndocs``
        survivors entering exact scoring."""
        approx = stage3_approx_score_batch(scores_c, codes, valid, q_valid)
        approx = jnp.where(cand >= 0, approx, -jnp.inf)
        ndocs = min(self.params.ndocs, self.params.candidate_cap)
        _, keep = jax.lax.top_k(approx, ndocs)
        return jnp.take_along_axis(cand, keep, axis=1)        # (Bp, ndocs)

    def gather_tokens_batch(self, pids):
        """Residual gather (host-bound in mmap mode — the only stage
        that faults residual pages; one deduplicated gather per batch)."""
        if self.device_resident:
            dev_pids = pids if isinstance(pids, jax.Array) \
                else jnp.asarray(pids)
            return self._gather_device_batch(dev_pids)
        c_np, r_np, v_np = self._dedup_gather(np.asarray(pids),
                                              codes_only=False)
        return jnp.asarray(c_np), jnp.asarray(r_np), jnp.asarray(v_np)

    def exact_score_gathered(self, q, q_valid, codes, packed, valid,
                             final_pids):
        """Stage 4 (device): fused decompress + MaxSim over gathered
        candidate tokens; -inf at padded candidate slots."""
        exact = decompress_maxsim_scores_batch(
            q, packed, codes.astype(jnp.int32), valid, self.centroids,
            self.bucket_weights, nbits=self.index.nbits, q_valid=q_valid)
        return jnp.where(final_pids >= 0, exact, -jnp.inf)

    def finalize_topk(self, exact, final_pids, B: int, k: int):
        """Terminal fuse: per-query top-k and (-1, -inf)-padded (B, k)
        host arrays."""
        ndocs = min(self.params.ndocs, self.params.candidate_cap)
        k_eff = min(k, ndocs)
        top_s, idx = jax.lax.top_k(exact, k_eff)
        out_pids = np.full((B, k), -1, np.int64)
        out_scores = np.full((B, k), -np.inf, np.float32)
        out_pids[:, :k_eff] = np.asarray(
            jnp.take_along_axis(final_pids, idx, axis=1))[:B]
        out_scores[:, :k_eff] = np.asarray(top_s)[:B]
        return out_pids, out_scores

    def score_gathered_lazy(self, q, q_valid, codes, packed, valid,
                            pids_p):
        """Rerank scoring over already-gathered tokens, returned as the
        *lazy* device value: the jitted dispatch returns immediately
        (async on every backend, CPU included) and the caller syncs when
        it first touches the result — a GIL-releasing wait, so the
        pipeline's host worker gathers the next micro-batch while the
        device executes this one."""
        scores = decompress_maxsim_scores_batch(
            q, packed, codes.astype(jnp.int32), valid, self.centroids,
            self.bucket_weights, nbits=self.index.nbits, q_valid=q_valid)
        return jnp.where(jnp.asarray(pids_p) >= 0, scores, -jnp.inf)

    # -- fused stage-4 tail (rerank_backend="fused") -----------------------
    def fused_topk_gathered(self, q, q_valid, codes, packed, valid,
                            cand_mask, k: int):
        """Fused stage-4 tail: decompress + MaxSim + per-query top-k as
        ONE device dispatch — the tiled ``fused_rerank`` Pallas kernel
        on TPU (no materialised (B, C) scores), the same fused XLA
        computation elsewhere. ``cand_mask``: host (Bp, C) bool
        (``pids >= 0``). Returns *lazy* (scores (Bp, kk), idx (Bp, kk)
        into the candidate axis), kk = min(k, C), selection and tie
        order bitwise-identical to :meth:`exact_score_gathered` +
        ``lax.top_k``."""
        with TraceAnnotation("tail:dispatch"):
            return fused_rerank_topk_batch(
                q, packed, codes.astype(jnp.int32), valid,
                jnp.asarray(cand_mask), self.centroids, self.bucket_weights,
                nbits=self.index.nbits, k=min(k, cand_mask.shape[1]),
                q_valid=q_valid)

    def fused_hybrid_topk_gathered(self, q, q_valid, codes, packed, valid,
                                   cand_mask, s_scores, alphas, k: int,
                                   b: int, normalizer: str):
        """Hybrid fused tail (see :func:`fused_hybrid_tail`): scoring +
        α-fusion + top-k in one dispatch. Returns lazy (scores (b, kk),
        idx (b, kk)), kk = min(k, first_k)."""
        with TraceAnnotation("tail:dispatch"):
            return fused_hybrid_tail(
                q, packed, codes.astype(jnp.int32), valid,
                jnp.asarray(cand_mask), self.centroids, self.bucket_weights,
                q_valid, jnp.asarray(s_scores), jnp.asarray(alphas),
                nbits=self.index.nbits, k=min(k, cand_mask.shape[1]), b=b,
                normalizer=normalizer)

    def finalize_topk_fused(self, top_s, top_i, final_np, B: int, k: int):
        """Terminal formatting for the fused tail: map candidate-axis
        indices back to pids and pad to the (B, k) (-1, -inf) contract —
        the fused counterpart of :meth:`finalize_topk`, minus its
        ``lax.top_k``/``take_along_axis`` dispatches (selection already
        happened inside the fused kernel)."""
        kk = top_i.shape[1]
        out_pids = np.full((B, k), -1, np.int64)
        out_scores = np.full((B, k), -np.inf, np.float32)
        s_np = np.asarray(top_s)[:B]
        i_np = np.asarray(top_i)[:B]
        out_pids[:, :kk] = np.take_along_axis(
            final_np[:B], np.clip(i_np, 0, None).astype(np.int64), axis=1)
        out_pids[:, :kk][i_np < 0] = -1
        out_scores[:, :kk] = s_np
        return out_pids, out_scores

    # -- deduplicated host gather (shared mmap pages per batch) ------------
    def _dedup_gather(self, pids_b: np.ndarray, *, codes_only: bool):
        """pids_b (B, C) (−1 pad) → per-query (codes (B, C, Ld),
        packed (B, C, Ld, pd) | None, valid (B, C, Ld)) through ONE
        PagedStore gather over the deduplicated pid set, so co-batched
        queries fault each index page at most once."""
        real = pids_b[pids_b >= 0]
        uniq = np.unique(real) if real.size else np.zeros(1, np.int64)
        if codes_only:
            codes_u, valid_u = self.index.gather_doc_codes(uniq)
            packed_u = None
        else:
            codes_u, packed_u, valid_u = self.index.gather_doc_tokens(uniq)
        pos = np.searchsorted(uniq, np.clip(pids_b, 0, None))
        pos = np.minimum(pos, len(uniq) - 1)
        mask = (pids_b >= 0)[..., None]
        codes = codes_u[pos]
        valid = valid_u[pos] & mask
        packed = None if packed_u is None else packed_u[pos]
        self.index.store.stats.transfer(
            *(a for a in (codes, packed, valid) if a is not None))
        return codes, packed, valid

    # -- device-resident gather --------------------------------------------
    def _gather_device(self, pids):
        idx = self.index
        safe = jnp.clip(pids, 0, idx.n_docs - 1)
        starts = self.dev_offsets[safe]
        tok = starts[:, None] + jnp.arange(idx.doc_maxlen)[None, :]
        tok = jnp.minimum(tok, idx.store.n_tokens - 1)
        codes = self.dev_codes[tok]
        packed = self.dev_residuals[tok]
        valid = (jnp.arange(idx.doc_maxlen)[None, :] <
                 self.dev_doclens[safe][:, None]) & (pids >= 0)[:, None]
        return codes, packed, valid

    def _gather_device_batch(self, pids):
        """pids (B, C) → device arrays reshaped to (B, C, Ld[, pd])."""
        B, C = pids.shape
        codes, packed, valid = self._gather_device(pids.reshape(-1))
        ld = self.index.doc_maxlen
        return (codes.reshape(B, C, ld), packed.reshape(B, C, ld, -1),
                valid.reshape(B, C, ld))
