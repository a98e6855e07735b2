"""Serving-level parity for the fused stage-4 tail: the
``rerank_backend="fused"`` plans must return *bitwise* the split-path
results — pids AND score bits — for all four methods, mixed batches,
per-query alpha, ragged candidate lists, shard groups, and the
process-worker backend. Also covers the Pallas-unavailable fallback
and the dispatch-count accounting the fusion exists to shrink."""

import numpy as np
import pytest

from repro.core.multistage import (
    METHODS,
    MultiStageParams,
    MultiStageRetriever,
)
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.sharded import build_shard_group, build_sharded_retriever
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.sharding import shard_boundaries, split_index_tree
from repro.index.splade_index import SpladeIndex, build_splade_index

PLAID = PlaidParams(nprobe=8, candidate_cap=512, ndocs=128, k=50)
MS = MultiStageParams(first_k=50, k=20)


@pytest.fixture(scope="module")
def base_dir(tmp_path_factory, small_corpus):
    base = tmp_path_factory.mktemp("fused_base")
    build_colbert_index(base / "colbert", small_corpus["doc_embs"],
                        small_corpus["doc_lens"], nbits=4,
                        n_centroids=128, kmeans_iters=4)
    build_splade_index(small_corpus["doc_term_ids"],
                       small_corpus["doc_term_weights"],
                       small_corpus["cfg"].vocab,
                       small_corpus["cfg"].n_docs).save(base / "splade")
    return base


@pytest.fixture(scope="module")
def retr(base_dir):
    index = ColBERTIndex(base_dir / "colbert", mode="mmap")
    sidx = SpladeIndex.load(base_dir / "splade", mmap=True)
    return MultiStageRetriever(sidx, PLAIDSearcher(index, PLAID), MS)


def _batch(corpus, lo, hi):
    return dict(q_embs=corpus["q_embs"][lo:hi],
                term_ids=corpus["q_term_ids"][lo:hi],
                term_weights=corpus["q_term_weights"][lo:hi])


def _both_backends(retriever, *args, **kw):
    """Run search_batch under fused then split, restoring the default."""
    retriever.set_rerank_backend("fused")
    fused = retriever.search_batch(*args, **kw)
    retriever.set_rerank_backend("split")
    try:
        split = retriever.search_batch(*args, **kw)
    finally:
        retriever.set_rerank_backend(retriever.params.rerank_backend)
    return fused, split


def _assert_bitwise(a, b):
    np.testing.assert_array_equal(np.asarray(a[0]), np.asarray(b[0]))
    np.testing.assert_array_equal(
        np.asarray(a[1], np.float32).view(np.uint32),
        np.asarray(b[1], np.float32).view(np.uint32))


# ---------------------------------------------------------------------------
# fused == split, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
def test_fused_matches_split_bitwise(retr, small_corpus, method):
    fused, split = _both_backends(retr, method, k=10,
                                  **_batch(small_corpus, 0, 12))
    _assert_bitwise(fused, split)


def test_fused_matches_split_mixed_batch(retr, small_corpus):
    methods = [METHODS[i % 4] for i in range(12)]
    fused, split = _both_backends(retr, methods, k=10,
                                  **_batch(small_corpus, 0, 12))
    _assert_bitwise(fused, split)


def test_fused_matches_split_per_query_alpha(retr, small_corpus):
    alphas = [0.0, 0.25, None, 1.0, 0.6, 0.1]
    fused, split = _both_backends(retr, "hybrid", alpha=alphas, k=15,
                                  **_batch(small_corpus, 6, 12))
    _assert_bitwise(fused, split)


@pytest.mark.parametrize("k", [1, 50, 200])
def test_fused_matches_split_depth_extremes(retr, small_corpus, k):
    """k == 1, k == first_k, and k far past the candidate count (ragged
    -1-padded candidate lists, (-inf, -1) tails)."""
    for method in ("rerank", "hybrid", "colbert"):
        fused, split = _both_backends(retr, method, k=k,
                                      **_batch(small_corpus, 0, 5))
        _assert_bitwise(fused, split)


def test_fused_single_query_matches_batch_row(retr, small_corpus):
    retr.set_rerank_backend("fused")
    batch = retr.search_batch("hybrid", k=10, **_batch(small_corpus, 0, 4))
    for i in range(4):
        one = retr.search_batch("hybrid", k=10,
                                **_batch(small_corpus, i, i + 1))
        np.testing.assert_array_equal(batch[0][i], one[0][0])
        np.testing.assert_array_equal(batch[1][i], one[1][0])


# ---------------------------------------------------------------------------
# shard groups
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_fused_matches_split_sharded(base_dir, small_corpus, n_shards):
    n_docs = small_corpus["cfg"].n_docs
    if n_shards == 1:
        dirs = [base_dir]
    else:
        group = split_index_tree(base_dir, n_shards,
                                 group_dir=base_dir / f"fs{n_shards}")
        dirs = [group / str(i) for i in range(n_shards)]
    g = build_sharded_retriever(dirs, shard_boundaries(n_docs, n_shards),
                                mode="mmap", plaid_params=PLAID,
                                multistage_params=MS)
    assert g.rerank_backend in ("fused", "split")   # resolved at init
    for method in METHODS:
        fused, split = _both_backends(g, method, k=10,
                                      **_batch(small_corpus, 0, 8))
        _assert_bitwise(fused, split)


def test_fused_matches_split_process_group(base_dir, small_corpus):
    group = split_index_tree(base_dir, 2, group_dir=base_dir / "fsp2")
    g = build_shard_group(
        [group / str(i) for i in range(2)],
        shard_boundaries(small_corpus["cfg"].n_docs, 2),
        workers="process", mode="mmap", plaid_params=PLAID,
        multistage_params=MS)
    try:
        assert g.rerank_backend in ("fused", "split")
        for method in ("hybrid", "colbert"):
            fused, split = _both_backends(g, method, k=10,
                                          **_batch(small_corpus, 0, 6))
            _assert_bitwise(fused, split)
    finally:
        g.close()


# ---------------------------------------------------------------------------
# knob semantics + accounting
# ---------------------------------------------------------------------------

def test_rerank_backend_validation(retr):
    """Both tails are selectable on every platform; nothing degrades
    one into the other, and an unknown name is refused."""
    with pytest.raises(ValueError):
        retr.set_rerank_backend("nope")
    assert retr.rerank_backend == "fused"
    retr.set_rerank_backend("split")
    try:
        assert retr.rerank_backend == "split"
    finally:
        retr.set_rerank_backend(retr.params.rerank_backend)
    assert retr.rerank_backend == "fused"


def test_fused_path_records_single_device_dispatch(retr, small_corpus):
    retr.set_rerank_backend("fused")
    retr.reset_stage_stats()
    retr.search_batch("rerank", k=10, **_batch(small_corpus, 0, 4))
    retr.search_batch("colbert", k=10, **_batch(small_corpus, 0, 4))
    stages = retr.pipeline_stats.snapshot()["stages"]
    assert "fuse_topk" not in stages            # zero on the fused path
    assert stages["fused_rerank"]["dispatches"] == 2
    assert stages["fused_rerank"]["device_dispatches"] == 2
    assert stages["fused_rerank:sync"]["device_dispatches"] == 0

    retr.set_rerank_backend("split")
    try:
        retr.reset_stage_stats()
        retr.search_batch("hybrid", k=10, **_batch(small_corpus, 0, 4))
        stages = retr.pipeline_stats.snapshot()["stages"]
        assert stages["device_score:maxsim"]["device_dispatches"] == 4
        assert stages["fuse_topk"]["device_dispatches"] == 0
    finally:
        retr.set_rerank_backend(retr.params.rerank_backend)
