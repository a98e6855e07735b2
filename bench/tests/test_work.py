"""Operations and bytes of the stage-4 tail against counts by hand."""

import work

PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_hybrid_tail_at_served_shapes():
    # B=8 queries of 32 x 128, 200 candidates of 180 token slots, 2-bit
    # residuals (32 bytes a token), 2^15 centroids, top 100
    flops, nbytes = work.tail_work(B=8, C=200, Ld=180, pd=32, Lq=32,
                                   dim=128, K=32768, k=100, nbits=2)
    slots = 8 * 200 * 180                       # 288,000 token slots
    assert flops == 2 * 288_000 * 32 * 128      # 2,359,296,000
    assert nbytes == (288_000 * 32              # packed residuals
                      + 288_000 * 4             # centroid ids
                      + 288_000                 # token validity
                      + 8 * 200                 # candidate mask
                      + 8 * 32 * 128 * 4        # queries
                      + 8 * 32                  # query validity
                      + 32768 * 128 * 4         # centroid table
                      + 4 * 4                   # bucket weights
                      + 8 * 100 * 8)            # top-k out
    assert nbytes == 9_216_000 + 1_152_000 + 288_000 + 1_600 + 131_072 \
        + 256 + 16_777_216 + 16 + 6_400
    assert slots == 288_000
    t, bound = work.least_time(flops, nbytes, PEAK)
    assert bound == "memory"
    assert t == nbytes / 819e9


def test_centroid_rows_cap_at_token_slots():
    # one query, one candidate of 4 slots: at most 4 centroid rows read
    _, nbytes = work.tail_work(B=1, C=1, Ld=4, pd=32, Lq=32, dim=128,
                               K=32768, k=1, nbits=2)
    assert nbytes == 4 * 32 + 4 * 4 + 4 + 1 + 32 * 128 * 4 + 32 \
        + 4 * 128 * 4 + 4 * 4 + 8


def test_compute_bound_when_operations_dominate():
    t, bound = work.least_time(197e12, 1.0, PEAK)
    assert bound == "compute" and t == 1.0
