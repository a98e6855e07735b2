"""Operations and bytes of the stage-4 tail, from the shapes of a call.

The work is counted from what the stage takes and gives, not from how
today's implementation computes it, so it stays the same whatever
implements the tail:

* operations: the MaxSim dot products of every query token with every
  gathered token slot, ``2·B·C·Ld·Lq·dim`` (multiply and add);
* bytes: each input read once and the output written once — packed
  residuals ``B·C·Ld·pd`` (uint8), centroid ids ``B·C·Ld·4`` (int32),
  token validity ``B·C·Ld`` and candidate mask ``B·C`` (bool), queries
  ``B·Lq·dim·4`` and their validity ``B·Lq``, the centroid rows the
  tokens can name, ``min(K, B·C·Ld)·dim·4``, the ``2^nbits`` bucket
  weights, and the top-k out, ``B·k·8`` (float32 score, int32 index).

The least time of a call is the larger of its operations over the
chip's peak rate and its bytes over the peak memory bandwidth
(``peaks.json``); the bound that gives the larger is the one that binds.
"""

from __future__ import annotations


def tail_work(B, C, Ld, pd, Lq, dim, K, k, nbits, **_) -> tuple[int, int]:
    """→ (operations, bytes) of one tail call."""
    slots = B * C * Ld
    flops = 2 * slots * Lq * dim
    nbytes = (slots * pd + slots * 4 + slots + B * C
              + B * Lq * dim * 4 + B * Lq
              + min(K, slots) * dim * 4 + (1 << nbits) * 4
              + B * k * 8)
    return flops, nbytes


def least_time(flops: int, nbytes: int, peak: dict) -> tuple[float, str]:
    """→ (seconds, the bound that binds: "compute" or "memory")."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
