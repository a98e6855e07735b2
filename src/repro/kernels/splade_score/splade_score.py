"""Pallas TPU kernel: block-partitioned impact scoring (the PISA
adaptation).

PISA's WAND-style scoring is pointer-chasing over compressed posting
lists — hostile to a vector unit. The TPU-native re-think partitions
the *score vector* over a grid of doc-id blocks; each grid step scans
every (query-term, posting) pair once and accumulates the entries whose
pid falls inside its block. The scatter becomes a dense one-hot matmul
on the MXU. A block of ``block_d = 128·H`` doc ids is laid out as an
``(H, 128)`` panel and a local pid splits into ``hi = pid // 128``,
``lo = pid % 128``, so for a chunk of postings

    panel[hi, lo] += Σ_e [hi_e = hi]·v_e · [lo_e = lo]
                   = (A · Bᵀ)[hi, lo],   A (H, chunk), B (128, chunk)

— an ``(H, chunk)·(chunk, 128)`` product instead of a matrix-vector
product against a ``(chunk, block_d)`` one-hot. The dot runs at
``Precision.HIGHEST``: ``B`` is exact in bf16 and the three-way bf16
split of ``A`` carries every float32 bit of ``w·imp``, so each panel
entry is a float32 sum of the same terms the segment-sum reference
adds, in another order (a few ulp apart).

The grid is ``(B, n_blocks)``: each (b, i) step owns query b's
postings and doc block i, so a cross-query micro-batch costs one
dispatch.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128


def _batch_kernel(pids_ref, vals_ref, out_ref, *, block_d: int):
    # pids/vals blocks (1, R, chunk): R chunks of one query's postings
    # (−1 / 0 padded); out block (1, 1, H, LANES) = doc block i
    H = block_d // LANES
    R, chunk = pids_ref.shape[1:]
    lo_base = pl.program_id(1) * block_d
    hi_iota = jax.lax.broadcasted_iota(jnp.int32, (H, chunk), 0)
    lo_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, chunk), 0)

    def row(r, acc):
        local = pids_ref[0, pl.ds(r, 1), :] - lo_base           # (1, chunk)
        v = vals_ref[0, pl.ds(r, 1), :]
        # out-of-block and −1 pids give hi outside [0, H): a zero row of A
        a = jnp.where((local >> 7) == hi_iota, v, 0.0)            # (H, chunk)
        b = ((local & (LANES - 1)) == lo_iota).astype(jnp.float32)
        return acc + jax.lax.dot_general(
            a, b, (((1,), (1,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)

    out_ref[0, 0] = jax.lax.fori_loop(0, R, row,
                                      jnp.zeros((H, LANES), jnp.float32))


@functools.partial(jax.jit,
                   static_argnames=("n_docs", "block_d", "interpret"))
def splade_block_pallas_batch(post_pids, post_vals, *, n_docs: int,
                              block_d: int = 2048, interpret: bool = False):
    """Batched stage-1 dispatch: post_pids (B, R, chunk) int32 (−1 pad);
    post_vals (B, R, chunk) f32 (weight pre-multiplied, 0 at padding) →
    (B, n_docs_padded) f32; caller slices [:, :n_docs]. ``block_d`` is a
    multiple of 128. One kernel launch for the whole micro-batch."""
    B, R, chunk = post_pids.shape
    assert block_d % LANES == 0, block_d
    H = block_d // LANES
    n_blocks = -(-n_docs // block_d)
    out = pl.pallas_call(
        functools.partial(_batch_kernel, block_d=block_d),
        grid=(B, n_blocks),
        in_specs=[
            pl.BlockSpec((1, R, chunk), lambda b, i: (b, 0, 0)),
            pl.BlockSpec((1, R, chunk), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, H, LANES), lambda b, i: (b, i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_blocks, H, LANES),
                                       jnp.float32),
        interpret=interpret,
    )(post_pids, post_vals)
    return out.reshape(B, n_blocks * block_d)
