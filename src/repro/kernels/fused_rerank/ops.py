"""Public wrapper for the fused decompress+MaxSim+top-k rerank tail.

``impl`` selection follows the repo convention: ``auto`` takes the
Pallas kernel on TPU and the fused-XLA reference elsewhere (same fused
semantics, one dispatch either way); ``interpret`` runs the kernel body
Mosaic-free for tests.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.utils import round_up
from repro.kernels import resolve_impl
from repro.kernels.decompress_maxsim.decompress_maxsim import LANES
from repro.kernels.fused_rerank.fused_rerank import fused_rerank_pallas_batch
from repro.kernels.fused_rerank.ref import (
    _pad_topk,
    fused_rerank_batch_ref,
    fused_rerank_ref,
)


def _empty_topk(lead, k: int):
    shape = lead + (k,)
    return (jnp.full(shape, -jnp.inf, jnp.float32),
            jnp.full(shape, -1, jnp.int32))


@functools.partial(jax.jit, static_argnames=("nbits", "k", "impl"))
def fused_rerank_topk(q, packed, cids, doc_valid, cand_mask, centroids,
                      bucket_weights, *, nbits: int, k: int, q_valid=None,
                      impl: str = "auto"):
    """Fused rerank tail over one query's compressed candidates.

    q: (Lq, d); packed: (C, Ld, d·nbits/8) uint8; cids: (C, Ld) int32;
    doc_valid: (C, Ld) bool; cand_mask: (C,) bool → (scores (k,) f32
    desc, idx (k,) i32) — ``lax.top_k`` of the -inf-masked MaxSim
    scores, ``(-inf, -1)``-padded when ``k > C``.
    """
    if q_valid is None:
        q_valid = jnp.ones((q.shape[0],), bool)
    if resolve_impl(impl) == "ref":
        return fused_rerank_ref(q, packed, cids, doc_valid, cand_mask,
                                centroids, bucket_weights, nbits, k,
                                q_valid)
    vals, idx = fused_rerank_topk_batch(
        q[None], packed[None], cids[None], doc_valid[None], cand_mask[None],
        centroids, bucket_weights, nbits=nbits, k=k, q_valid=q_valid[None],
        impl=impl)
    return vals[0], idx[0]


@functools.partial(jax.jit, static_argnames=("nbits", "k", "impl"))
def fused_rerank_topk_batch(q, packed, cids, doc_valid, cand_mask,
                            centroids, bucket_weights, *, nbits: int,
                            k: int, q_valid=None, impl: str = "auto"):
    """Cross-query batched fused tail — the stage-4 single dispatch.

    q: (B, Lq, d); packed: (B, C, Ld, d·nbits/8) uint8; cids/doc_valid:
    (B, C, Ld); cand_mask: (B, C) bool; q_valid: optional (B, Lq) bool
    → (scores (B, k) f32 desc, idx (B, k) i32 into the candidate axis).
    """
    impl = resolve_impl(impl)
    B, C = packed.shape[:2]
    kk = min(k, C)
    if kk == 0:
        return _empty_topk((B,), k)
    if q_valid is None:
        q_valid = jnp.ones(q.shape[:2], bool)
    if impl == "ref":
        return fused_rerank_batch_ref(q, packed, cids, doc_valid,
                                      cand_mask, centroids,
                                      bucket_weights, nbits, k, q_valid)
    # running-state width padded to whole lane rows; the top-kk prefix
    # of a top-kp selection is the top-kk selection, so slicing is exact
    vals, idx = fused_rerank_pallas_batch(
        q, packed, cids, doc_valid, cand_mask, q_valid, centroids,
        bucket_weights, nbits=nbits, kp=round_up(kk, LANES),
        interpret=(impl == "interpret"))
    return _pad_topk(vals[:, :kk], idx[:, :kk], k)
