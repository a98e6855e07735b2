"""Pallas TPU kernel: fused residual decompression + MaxSim.

This is the TPU-native adaptation of the paper's memory-mapping insight.
On the CPU system, mmap avoids materialising the index in RAM; on TPU
the equivalent waste is materialising *decompressed fp32 embeddings* in
HBM between a decompression op and a scoring op. The fusion keeps the
decoded residuals strictly in VMEM.

The centroid table (2^15..2^17 rows × d f32 = 16..64 MiB) never enters
the kernel. A decoded token is ``c + r`` (centroid plus residual), so

    q·(c + r) = q·c + q·r

and the ``q·c`` term is a gather from the per-query centroid-score
table ``Q·Cᵀ`` (the table PLAID's stage-1 probe computes), laid out as
``(K, Lq)`` so that a document token's centroid id selects one whole
row of ``Lq`` scores: one gather index per token, not one per gathered
float (a gather's cost on the chip follows its number of indices). The
gather runs in XLA before the kernel (``kernel_operands``); the kernel
decodes only the residual bucket codes, for which a ``2^nbits``-entry
weight table in SMEM suffices.

Layout: candidates sit on the 128 lanes of a vreg, tokens on a leading
axis. One grid step scores one tile of ``LANES`` candidates of one
query: for each document token ``t`` it decodes the ``(d, LANES)``
residual panel, takes one ``(Lq, d)·(d, LANES)`` MXU product, adds the
gathered ``q·c`` panel and folds the result into a running
``(Lq, LANES)`` max. Every block's last two dimensions are either the
whole array dimension or multiples of (8, 128), as Mosaic requires.

Precision: every dot runs at ``Precision.HIGHEST`` (full float32; the
TPU default for float32 operands is a single bf16 pass), here and in
the jnp references, so kernel and reference differ only in summation
order and in rounding ``c + r`` before the product. That bounds the
difference by a few float32 ulp of the score's magnitude; see
:func:`score_atol`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.common.utils import round_up

NEG = -1e30
LANES = 128          # candidates per tile: one vreg row of lanes
HIGHEST = jax.lax.Precision.HIGHEST
# Ulps of headroom in :func:`score_atol`. Kernel and reference round
# differently in three places (c + r before the product, q·c and q·r as
# two sums, the sum over query tokens), each worth at most a couple of
# ulps of the score's scale at these sizes.
SCORE_ULPS = 8


def score_atol(ref_scores) -> float:
    """Absolute tolerance between a kernel's MaxSim scores and the
    float32 reference: ``SCORE_ULPS`` float32 ulps of the largest
    reference magnitude. Both sides compute every dot in full float32
    (``Precision.HIGHEST``) and differ only in rounding order."""
    finite = np.abs(np.asarray(ref_scores, np.float32))
    finite = finite[np.isfinite(finite)]
    scale = np.float32(finite.max()) if finite.size else np.float32(1)
    return float(SCORE_ULPS * np.spacing(max(scale, np.float32(1))))


def kernel_operands(q, packed, cids, valid, q_valid, centroids, nbits: int):
    """XLA-side preparation shared by the MaxSim kernels.

    q (B, Lq, d); packed (B, C, Ld, pd) u8; cids/valid (B, C, Ld);
    q_valid (B, Lq) → (q_perm (B, Lq, d) f32,
    packed_t (B, T, Ld, pd, LANES) u8, qc (B, T, Ld, Lq, LANES) f32)
    with T = ceil(C / LANES).

    * ``q·c`` is a row gather from the centroid-score table transposed
      to ``(B, K, Lq)``: each document token's centroid id fetches its
      ``Lq`` scores as one row, so the gather takes one index per token
      rather than one per float; the rows are then moved into the
      kernel's candidate-on-lanes layout. The values are the table's,
      bit for bit.
    * Invalid query tokens are zeroed in ``q`` and in the table, so they
      contribute ``max(0) = 0`` exactly as the reference's mask does.
    * Invalid document tokens (and padded candidates) get ``q·c = NEG``,
      which no residual term can lift back above ``NEG / 2``.
    * Byte ``j`` of a packed row holds the codes of dims
      ``j·cpb .. j·cpb + cpb - 1``; the kernel decodes shift group ``s``
      of every byte into rows ``s·pd + j``, so ``q``'s columns are
      permuted to match instead of interleaving codes in-kernel.
    """
    B, C, Ld, pd = packed.shape
    Lq, d = q.shape[1:]
    cpb = 8 // nbits
    Cp = round_up(max(C, 1), LANES)
    T = Cp // LANES
    qv = q_valid.astype(jnp.float32)
    q = q.astype(jnp.float32) * qv[..., None]
    table = jnp.einsum("bqd,kd->bqk", q, centroids.astype(jnp.float32),
                       precision=HIGHEST,
                       preferred_element_type=jnp.float32)   # (B, Lq, K)
    pad = Cp - C
    cids = jnp.pad(cids.astype(jnp.int32), ((0, 0), (0, pad), (0, 0)))
    valid = jnp.pad(valid.astype(bool), ((0, 0), (0, pad), (0, 0)))
    packed = jnp.pad(packed, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # one index per document token: whole rows of Lq scores
    rows = table.transpose(0, 2, 1)                  # (B, K, Lq)
    qc = rows[jnp.arange(B)[:, None, None], cids]    # (B, Cp, Ld, Lq)
    qc = jnp.where(valid[..., None], qc, NEG)
    # (B, Cp, ...) → (B, T, Ld, Lq, LANES): candidate on the lane axis
    qc = qc.reshape(B, T, LANES, Ld, Lq).transpose(0, 1, 3, 4, 2)
    packed_t = packed.reshape(B, T, LANES, Ld, pd).transpose(0, 1, 3, 4, 2)
    q_perm = q.reshape(B, Lq, pd, cpb).transpose(0, 1, 3, 2).reshape(
        B, Lq, d)
    return q_perm, packed_t, qc


def tile_scores(q_ref, packed_ref, qc_ref, w_ref, nbits: int):
    """Shared kernel body: MaxSim scores of one candidate tile.

    q_ref (1, Lq, d); packed_ref (1, 1, Ld, pd, LANES) u8;
    qc_ref (1, 1, Ld, Lq, LANES); w_ref (2^nbits,) SMEM → (1, LANES)."""
    q = q_ref[0]
    Lq = q.shape[0]
    Ld = packed_ref.shape[2]
    cpb = 8 // nbits
    mask = (1 << nbits) - 1

    def token(t, m):
        x = packed_ref[0, 0, t].astype(jnp.int32)          # (pd, LANES)
        groups = []
        for s in range(cpb):
            code = (x >> (s * nbits)) & mask
            r = jnp.zeros(code.shape, jnp.float32)
            for v in range(1 << nbits):
                r = jnp.where(code == v, w_ref[v], r)
            groups.append(r)
        r = jnp.concatenate(groups, axis=0)                 # (d, LANES)
        sim = jax.lax.dot(q, r, precision=HIGHEST,
                          preferred_element_type=jnp.float32)
        return jnp.maximum(m, sim + qc_ref[0, 0, t])

    m = jax.lax.fori_loop(0, Ld, token,
                          jnp.full((Lq, LANES), NEG, jnp.float32))
    m = jnp.where(m <= NEG / 2, 0.0, m)
    return jnp.sum(m, axis=0, keepdims=True)


def operand_specs(Lq: int, d: int, Ld: int, pd: int):
    """BlockSpecs of (q_perm, packed_t, qc, bucket weights) for a
    ``(B, T)`` grid."""
    return [
        pl.BlockSpec((1, Lq, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, 1, Ld, pd, LANES), lambda b, i: (b, i, 0, 0, 0)),
        pl.BlockSpec((1, 1, Ld, Lq, LANES), lambda b, i: (b, i, 0, 0, 0)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ]


def _batch_kernel(q_ref, packed_ref, qc_ref, w_ref, out_ref, *, nbits):
    out_ref[0] = tile_scores(q_ref, packed_ref, qc_ref, w_ref, nbits)


@functools.partial(jax.jit, static_argnames=("nbits", "interpret"))
def decompress_maxsim_pallas_batch(q, packed, cids, valid, q_valid,
                                   centroids, bucket_weights, *, nbits: int,
                                   interpret: bool = False):
    """Batched fused scoring: q (B, Lq, d); packed (B, C, Ld, pd) u8;
    cids/valid (B, C, Ld); q_valid (B, Lq) → (B, C) f32. The whole batch
    is one kernel launch — stage 4 scores B queries in one dispatch."""
    B, C, Ld, pd = packed.shape
    Lq, d = q.shape[1:]
    q_perm, packed_t, qc = kernel_operands(q, packed, cids, valid, q_valid,
                                           centroids, nbits)
    T = packed_t.shape[1]
    out = pl.pallas_call(
        functools.partial(_batch_kernel, nbits=nbits),
        grid=(B, T),
        in_specs=operand_specs(Lq, d, Ld, pd),
        out_specs=pl.BlockSpec((1, 1, LANES), lambda b, i: (b, 0, i)),
        out_shape=jax.ShapeDtypeStruct((B, 1, T * LANES), jnp.float32),
        interpret=interpret,
    )(q_perm, packed_t, qc, bucket_weights.astype(jnp.float32))
    return out[:, 0, :C]
