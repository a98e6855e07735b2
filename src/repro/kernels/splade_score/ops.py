"""Public wrappers for the SPLADE block-scoring kernel (single-query
and leading-batch-dim variants, plus a fused scores→top-k entry point
for the serving stage-1 path)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.common.utils import round_up
from repro.kernels import resolve_impl
from repro.kernels.splade_score.ref import (splade_block_scores_batch_ref,
                                            splade_block_scores_ref)
from repro.kernels.splade_score.splade_score import splade_block_pallas_batch


def _chunked(pids, vals, chunk: int):
    """Reshape (…, Qt, max_df) postings into (…, R, chunk) rows, padding
    the entry count up to a multiple of ``chunk`` with −1/0 entries."""
    *lead, Qt, max_df = pids.shape
    E = Qt * max_df
    pad = [(0, 0)] * len(lead) + [(0, round_up(E, chunk) - E)]
    pids = jnp.pad(pids.reshape(*lead, E), pad, constant_values=-1)
    vals = jnp.pad(vals.reshape(*lead, E), pad)
    return (pids.reshape(*lead, -1, chunk),
            vals.reshape(*lead, -1, chunk))


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "impl", "block_d", "chunk"))
def splade_block_scores(post_pids, post_imps, term_weights, *, n_docs: int,
                        impl: str = "auto", block_d: int = 2048,
                        chunk: int = 512):
    """Impact scores for one query over padded postings → (n_docs,) f32."""
    if resolve_impl(impl) == "ref":
        return splade_block_scores_ref(post_pids, post_imps, term_weights,
                                       n_docs)
    return splade_block_scores_batch(
        post_pids[None], post_imps[None], term_weights[None],
        n_docs=n_docs, impl=impl, block_d=block_d, chunk=chunk)[0]


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "impl", "block_d", "chunk"))
def splade_block_scores_batch(post_pids, post_imps, term_weights, *,
                              n_docs: int, impl: str = "auto",
                              block_d: int = 2048, chunk: int = 512):
    """Cross-query batched impact scores.

    post_pids: (B, Qt, max_df) int32 (−1 pad); post_imps: (B, Qt, max_df)
    f32 (de-quantised); term_weights: (B, Qt) f32 (0 disables a term)
    → (B, n_docs) f32. One dispatch for the whole batch.
    """
    impl = resolve_impl(impl)
    if impl == "ref":
        return splade_block_scores_batch_ref(post_pids, post_imps,
                                             term_weights, n_docs)
    valid = (post_pids >= 0) & (term_weights[:, :, None] > 0)
    vals = jnp.where(valid, term_weights[:, :, None] * post_imps, 0.0)
    pids = jnp.where(valid, post_pids, -1)
    pids, vals = _chunked(pids, vals, chunk)
    out = splade_block_pallas_batch(pids.astype(jnp.int32),
                                    vals.astype(jnp.float32),
                                    n_docs=n_docs, block_d=block_d,
                                    interpret=(impl == "interpret"))
    return out[:, :n_docs]


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "k", "impl", "block_d",
                                    "chunk"))
def splade_block_topk_batch(post_pids, post_imps, term_weights, *,
                            n_docs: int, k: int, impl: str = "auto",
                            block_d: int = 2048, chunk: int = 512):
    """Fused stage-1 dispatch: batched block scoring + per-query top-k in
    one jitted computation → (pids (B, k) int32, scores (B, k) f32),
    descending. ``k`` must be ≤ ``n_docs`` (caller clamps/pads)."""
    scores = splade_block_scores_batch(post_pids, post_imps, term_weights,
                                       n_docs=n_docs, impl=impl,
                                       block_d=block_d, chunk=chunk)
    top_scores, top_pids = jax.lax.top_k(scores, k)
    return top_pids.astype(jnp.int32), top_scores
