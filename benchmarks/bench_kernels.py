"""Kernel-level benchmark: wall time of the jitted scoring paths on
this host (CPU; TPU numbers come from the dry-run roofline) plus the
analytic HBM-traffic comparison fused-vs-unfused that motivates the
decompress_maxsim kernel (the TPU adaptation of "don't materialise the
index")."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save
from repro.kernels.decompress_maxsim.ops import (
    decompress_maxsim_scores,
    decompress_maxsim_scores_batch,
)
from repro.kernels.fused_rerank.ops import fused_rerank_topk_batch
from repro.kernels.maxsim.ops import maxsim_scores
from repro.kernels.splade_score.ops import splade_block_scores


def _time(fn, *args, iters=20):
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def hbm_model(C, Ld, d, nbits, Lq):
    """Per-candidate HBM bytes: fused reads packed codes + cid; the
    unfused pipeline additionally writes+reads the fp32 embeddings."""
    packed = Ld * d * nbits // 8 + Ld * 4
    fp32 = Ld * d * 4
    return {"fused_bytes": C * packed,
            "unfused_bytes": C * (packed + 2 * fp32),
            "traffic_ratio": (packed + 2 * fp32) / packed}


def main(quick: bool = False):
    out = {}
    k = jax.random.PRNGKey(0)
    C, Ld, Lq, d, nbits = (256 if quick else 1024), 96, 32, 128, 4

    q = jax.random.normal(k, (Lq, d))
    docs = jax.random.normal(jax.random.fold_in(k, 1), (C, Ld, d))
    valid = jnp.ones((C, Ld), bool)
    t_maxsim = _time(lambda a, b, c: maxsim_scores(a, b, c, impl="ref"),
                     q, docs, valid)

    packed = jax.random.randint(jax.random.fold_in(k, 2),
                                (C, Ld, d * nbits // 8), 0, 256
                                ).astype(jnp.uint8)
    cids = jax.random.randint(jax.random.fold_in(k, 3), (C, Ld), 0, 4096)
    cent = jax.random.normal(jax.random.fold_in(k, 4), (4096, d))
    bw = jnp.linspace(-0.2, 0.2, 16)
    t_fused = _time(lambda *a: decompress_maxsim_scores(
        *a, nbits=nbits, impl="ref"), q, packed, cids, valid, cent, bw)

    pids = jax.random.randint(jax.random.fold_in(k, 5), (32, 512), -1,
                              100_000, jnp.int32)
    imps = jax.random.uniform(jax.random.fold_in(k, 6), (32, 512))
    w = jax.random.uniform(jax.random.fold_in(k, 7), (32,))
    t_splade = _time(lambda *a: splade_block_scores(
        *a, n_docs=100_000, impl="ref"), pids, imps, w)

    # fused rerank tail (decompress + MaxSim + top-k, one dispatch) vs
    # the split serving tail (score dispatch, eager mask, eager top-k):
    # identical results, so the comparison is pure wall + the peak
    # intermediate-tensor footprint between dispatches
    B, Ct, Ldt, k_top = (2, 128, 24, 50) if quick else (8, 256, 32, 100)
    qb = jax.random.normal(jax.random.fold_in(k, 8), (B, Lq, d))
    packed_b = jax.random.randint(
        jax.random.fold_in(k, 9), (B, Ct, Ldt, d * nbits // 8), 0, 256
        ).astype(jnp.uint8)
    cids_b = jax.random.randint(jax.random.fold_in(k, 10), (B, Ct, Ldt),
                                0, 4096)
    valid_b = jnp.ones((B, Ct, Ldt), bool)
    cmask_b = jnp.ones((B, Ct), bool)

    def split_tail(q_, p_, c_, v_, m_):
        s = decompress_maxsim_scores_batch(q_, p_, c_, v_, cent, bw,
                                           nbits=nbits, impl="ref")
        s = jnp.where(m_, s, -jnp.inf)
        return jax.lax.top_k(s, k_top)

    t_split = _time(split_tail, qb, packed_b, cids_b, valid_b, cmask_b)
    t_ftail = _time(lambda *a: fused_rerank_topk_batch(
        *a, cent, bw, nbits=nbits, k=k_top, impl="ref"),
        qb, packed_b, cids_b, valid_b, cmask_b)
    model = hbm_model(C, Ld, d, nbits, Lq)
    kp = -(-min(k_top, Ct) // 128) * 128     # whole lane rows
    rerank_model = {
        # split: the full (B, C) fp32 score tensor round-trips HBM
        # twice (raw + masked copy) before selection reads it back
        "rerank_split_scores_bytes": 2 * B * Ct * 4,
        # fused kernel: only the running (kp,) top-k state per query
        "rerank_fused_scores_bytes": B * kp * (4 + 4),
    }
    out.update({
        "maxsim_ms": t_maxsim * 1e3,
        "decompress_maxsim_ms": t_fused * 1e3,
        "splade_score_ms": t_splade * 1e3,
        "rerank_split_tail_ms": t_split * 1e3,
        "rerank_fused_tail_ms": t_ftail * 1e3,
        "rerank_tail_batch": B, "rerank_tail_candidates": Ct,
        "rerank_tail_k": k_top,
        "candidates": C, "doc_maxlen": Ld,
        **model, **rerank_model,
    })
    print(f"maxsim({C}x{Ld})           {t_maxsim * 1e3:8.2f} ms")
    print(f"decompress_maxsim({C}x{Ld}) {t_fused * 1e3:8.2f} ms")
    print(f"splade_score(32x512)      {t_splade * 1e3:8.2f} ms")
    print(f"rerank tail ({B}x{Ct}, k={k_top}): split "
          f"{t_split * 1e3:.2f} ms / fused {t_ftail * 1e3:.2f} ms; "
          f"peak scores bytes {rerank_model['rerank_split_scores_bytes']}"
          f" -> {rerank_model['rerank_fused_scores_bytes']}")
    print(f"fused vs unfused HBM traffic: {model['traffic_ratio']:.1f}x "
          f"less for the fused kernel")
    assert model["traffic_ratio"] > 10
    assert (rerank_model["rerank_fused_scores_bytes"]
            < rerank_model["rerank_split_scores_bytes"])
    save("kernels", out)
    return out


if __name__ == "__main__":
    main()
