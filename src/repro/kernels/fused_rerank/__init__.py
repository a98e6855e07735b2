"""Fused IO-aware rerank tail: decompress + MaxSim + per-query top-k
in one tiled dispatch (FLASH-MAXSIM-style; see fused_rerank.py)."""

from repro.kernels.fused_rerank.ops import (  # noqa: F401
    fused_rerank_topk,
    fused_rerank_topk_batch,
)
