"""Public wrapper for the fused decompress+MaxSim kernel (``impl``:
see :func:`repro.kernels.resolve_impl`)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import resolve_impl
from repro.kernels.decompress_maxsim.decompress_maxsim import (
    decompress_maxsim_pallas_batch,
)
from repro.kernels.decompress_maxsim.ref import (decompress_maxsim_batch_ref,
                                                 decompress_maxsim_ref)


@functools.partial(jax.jit, static_argnames=("nbits", "impl"))
def decompress_maxsim_scores(q, packed, cids, doc_valid, centroids,
                             bucket_weights, *, nbits: int,
                             q_valid=None, impl: str = "auto"):
    """Fused scoring over compressed candidates.

    q: (Lq, d); packed: (C, Ld, d·nbits/8) uint8; cids: (C, Ld) int32;
    doc_valid: (C, Ld) bool → (C,) f32 scores.
    """
    if q_valid is None:
        q_valid = jnp.ones((q.shape[0],), bool)
    if resolve_impl(impl) == "ref":
        return decompress_maxsim_ref(q, packed, cids, doc_valid, centroids,
                                     bucket_weights, nbits, q_valid)
    return decompress_maxsim_scores_batch(
        q[None], packed[None], cids[None], doc_valid[None], centroids,
        bucket_weights, nbits=nbits, q_valid=q_valid[None], impl=impl)[0]


@functools.partial(jax.jit, static_argnames=("nbits", "impl"))
def decompress_maxsim_scores_batch(q, packed, cids, doc_valid, centroids,
                                   bucket_weights, *, nbits: int,
                                   q_valid=None, impl: str = "auto"):
    """Cross-query batched fused scoring (the stage-4 batch dispatch).

    q: (B, Lq, d); packed: (B, C, Ld, d·nbits/8) uint8; cids: (B, C, Ld)
    int32; doc_valid: (B, C, Ld) bool; q_valid: optional (B, Lq) bool
    (False on padded query tokens) → (B, C) f32 scores.
    """
    impl = resolve_impl(impl)
    if q_valid is None:
        q_valid = jnp.ones(q.shape[:2], bool)
    if impl == "ref":
        return decompress_maxsim_batch_ref(q, packed, cids, doc_valid,
                                           centroids, bucket_weights, nbits,
                                           q_valid)
    return decompress_maxsim_pallas_batch(
        q, packed, cids, doc_valid, q_valid, centroids, bucket_weights,
        nbits=nbits, interpret=(impl == "interpret"))
