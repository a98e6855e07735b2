"""Pallas TPU kernel: fused decompress + MaxSim + running per-query
top-k over candidate tiles — the FLASH-MAXSIM-style rerank tail.

The split stage-4 tail runs three dispatches (decompress+MaxSim scores,
score masking, top-k selection) and materialises the full ``(B, C)``
score tensor in HBM between them. This kernel scores one tile of
``LANES`` candidates per grid step with the same body as
``decompress_maxsim`` (``tile_scores``: the ``q·c`` term, gathered in
XLA as whole ``Lq``-float rows of the ``(B, K, Lq)`` centroid-score
table, one index per document token, plus an in-VMEM residual decode,
so the centroid table never enters the kernel) and folds the tile into
a running per-query top-k held in the output block across grid steps.
Nothing wider than one ``(1, LANES)`` score row ever exists.

The running top-k merge is *sortless*: each grid step ranks the
``kp + LANES`` merged entries by pairwise comparison counts
(rank_j = #{m : (s_m, -i_m) ≻ (s_j, -i_j)}) and gathers entry ``j``
into output slot ``rank_j`` with a masked sum — O(n²) compares on the
VPU with n = kp + 128, no sort lowering required, and the (score desc,
index asc) tie order is exactly ``lax.top_k``'s. Candidate tiles arrive
in ascending index order and the running entries always carry lower
indices than the incoming tile, which is what makes the incremental
merge reproduce the global stable order.

Precision: the scores are those of ``decompress_maxsim`` (full float32
dots); they agree with the reference within
``decompress_maxsim.score_atol`` and the selected indices agree
wherever neighbouring scores differ by more than that.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.decompress_maxsim.decompress_maxsim import (
    LANES,
    kernel_operands,
    operand_specs,
    tile_scores,
)


def _merge_topk(prev_s, prev_i, tile_s, tile_i, kp: int):
    """Rank-selection merge of the running (1, kp) state with a scored
    (1, LANES) tile: top-``kp`` of the concatenation by (score desc,
    index asc). All indices are distinct, so ranks are a permutation
    and the masked sums gather exactly one entry per output slot (-inf
    survives the where-sum; no -inf·0 NaNs)."""
    ms = jnp.concatenate([prev_s, tile_s], axis=1)            # (1, n)
    mi = jnp.concatenate([prev_i, tile_i], axis=1)
    n = ms.shape[1]
    row_s = jnp.broadcast_to(ms, (n, n))                      # [j, m] = s_m
    row_i = jnp.broadcast_to(mi, (n, n))
    col_s, col_i = row_s.T, row_i.T                           # [j, m] = s_j
    beats = (row_s > col_s) | ((row_s == col_s) & (row_i < col_i))
    rank = jnp.sum(beats.astype(jnp.float32), axis=1, keepdims=True)
    slot = jax.lax.broadcasted_iota(jnp.int32, (1, kp), 1)
    sel = rank == slot.astype(jnp.float32)                    # (n, kp)
    out_s = jnp.sum(jnp.where(sel, col_s[:, :kp], 0.0), axis=0,
                    keepdims=True)
    out_i = jnp.sum(jnp.where(sel, col_i[:, :kp], 0), axis=0,
                    keepdims=True)
    return out_s, out_i


def _batch_kernel(q_ref, packed_ref, qc_ref, w_ref, cmask_ref, out_s_ref,
                  out_i_ref, *, nbits, kp):
    # grid (B, T): for a fixed batch row the candidate tiles run
    # consecutively, so the (1, 1, kp) output blocks stay VMEM-resident
    # as the running top-k state across the whole row
    i = pl.program_id(1)

    @pl.when(i == 0)
    def _():
        # sentinels lose every comparison: -inf scores with indices past
        # every candidate, so real candidates — even masked ones, which
        # tie at -inf but carry lower indices — always displace them
        total = pl.num_programs(1) * LANES
        out_s_ref[0] = jnp.full((1, kp), -jnp.inf, jnp.float32)
        out_i_ref[0] = total + jax.lax.broadcasted_iota(jnp.int32,
                                                        (1, kp), 1)

    s = tile_scores(q_ref, packed_ref, qc_ref, w_ref, nbits)
    s = jnp.where(cmask_ref[0] != 0, s, -jnp.inf)
    tile_i = i * LANES + jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    out_s_ref[0], out_i_ref[0] = _merge_topk(out_s_ref[0], out_i_ref[0], s,
                                             tile_i, kp)


@functools.partial(jax.jit, static_argnames=("nbits", "kp", "interpret"))
def fused_rerank_pallas_batch(q, packed, cids, valid, cmask, q_valid,
                              centroids, bucket_weights, *, nbits: int,
                              kp: int, interpret: bool = False):
    """Batched fused tail: q (B, Lq, d); packed (B, C, Ld, pd) u8;
    cids/valid (B, C, Ld); cmask (B, C) bool; q_valid (B, Lq) →
    (scores (B, kp), idx (B, kp)), the top-``kp`` of the masked MaxSim
    scores in (desc, index-asc) order. ``kp`` is a multiple of
    ``LANES``; slots past the candidate count hold (-inf, index ≥ C).
    One kernel launch reranks the whole micro-batch — the single device
    dispatch of the fused stage."""
    B, C, Ld, pd = packed.shape
    Lq, d = q.shape[1:]
    assert kp % LANES == 0, kp
    q_perm, packed_t, qc = kernel_operands(q, packed, cids, valid, q_valid,
                                           centroids, nbits)
    T = packed_t.shape[1]
    cm = jnp.pad(cmask.astype(jnp.int32), ((0, 0), (0, T * LANES - C)))
    out_spec = pl.BlockSpec((1, 1, kp), lambda b, i: (b, 0, 0))
    vals, idx = pl.pallas_call(
        functools.partial(_batch_kernel, nbits=nbits, kp=kp),
        grid=(B, T),
        in_specs=operand_specs(Lq, d, Ld, pd) + [
            pl.BlockSpec((1, 1, LANES), lambda b, i: (b, 0, i))],
        out_specs=(out_spec, out_spec),
        out_shape=(jax.ShapeDtypeStruct((B, 1, kp), jnp.float32),
                   jax.ShapeDtypeStruct((B, 1, kp), jnp.int32)),
        interpret=interpret,
    )(q_perm, packed_t, qc, bucket_weights.astype(jnp.float32),
      cm[:, None, :])
    return vals[:, 0], idx[:, 0]
