"""Request-lifecycle refactor: typed per-request contexts, the
coordinator cache hierarchy (exact result cache + stage-1/candidate
cache), SLO-aware admission/degradation, and the loadgen realism knobs.

The load-bearing contracts:

* an exact-cache hit is **bitwise** the cold answer (all four methods,
  mixed batches, per-query k/alpha keying);
* the LRU evicts at capacity and invalidates on index-generation bump;
* cache-on answers stay bitwise-parity across 1/2/4 thread shards and
  process workers;
* admission degrades hybrid/rerank to the splade-only plan (with a
  reason code) before it sheds, and sheds are never counted as
  failures by the load generators.
"""

import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.sharded import build_sharded_retriever, build_shard_group
from repro.eval.metrics import ndcg_at_k
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.sharding import (
    load_group,
    shard_boundaries,
    split_index_tree,
)
from repro.index.splade_index import SpladeIndex, build_splade_index
from repro.serving.admission import AdmissionController, RequestShed
from repro.serving.context import (
    ADMIT_DEGRADED,
    ADMIT_FULL,
    ADMIT_SHED,
    CacheHierarchy,
    LRUCache,
    query_digest,
)
from repro.serving.engine import Request, ServeEngine
from repro.serving.loadgen import (
    load_trace,
    run_poisson_load,
    zipf_trace,
)
from repro.serving.server import RetrievalServer

METHODS = ("splade", "rerank", "hybrid", "colbert")
PLAID = PlaidParams(nprobe=8, candidate_cap=512, ndocs=128, k=50)
MS = MultiStageParams(first_k=50, k=20)


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def base_dir(tmp_path_factory, small_corpus):
    base = tmp_path_factory.mktemp("reqcache_base")
    build_colbert_index(base / "colbert", small_corpus["doc_embs"],
                        small_corpus["doc_lens"], nbits=4,
                        n_centroids=128, kmeans_iters=4)
    build_splade_index(small_corpus["doc_term_ids"],
                       small_corpus["doc_term_weights"],
                       small_corpus["cfg"].vocab,
                       small_corpus["cfg"].n_docs).save(base / "splade")
    return base


def _fresh_retr(base_dir):
    index = ColBERTIndex(base_dir / "colbert", mode="mmap")
    sidx = SpladeIndex.load(base_dir / "splade", mmap=True)
    return MultiStageRetriever(sidx, PLAIDSearcher(index, PLAID), MS)


@pytest.fixture(scope="module")
def reference(base_dir, small_corpus):
    """Cache-free engine: the cold-answer oracle."""
    return ServeEngine(_fresh_retr(base_dir))


def _reqs(corpus, method, idxs, k=20, alpha=None, qid0=0):
    return [Request(qid=qid0 + j, method=method,
                    q_emb=corpus["q_embs"][i],
                    term_ids=corpus["q_term_ids"][i],
                    term_weights=corpus["q_term_weights"][i],
                    k=k, alpha=alpha)
            for j, i in enumerate(idxs)]


def _assert_bitwise(ref, got):
    np.testing.assert_array_equal(np.asarray(ref.pids),
                                  np.asarray(got.pids))
    r = np.asarray(ref.scores).view(np.uint32)
    g = np.asarray(got.scores).view(np.uint32)
    np.testing.assert_array_equal(r, g)


# ---------------------------------------------------------------------------
# LRU + context primitives
# ---------------------------------------------------------------------------

def test_lru_counters_eviction_and_generation_purge():
    c = LRUCache(2, name="t")
    assert c.get("a") is None and c.misses == 1
    c.put("a", 1, generation=0)
    c.put("b", 2, generation=0)
    assert c.get("a") == 1 and c.hits == 1
    c.put("c", 3, generation=1)          # evicts LRU ("b")
    assert c.evictions == 1
    assert c.get("b") is None
    assert c.purge_below(1) == 1         # "a" was generation 0
    assert c.invalidations == 1
    assert c.get("a") is None and c.get("c") == 3
    # advisory probe: a count_miss=False miss is free
    m = c.misses
    assert c.get("zzz", count_miss=False) is None
    assert c.misses == m


def test_lru_capacity_zero_disables():
    c = LRUCache(0)
    c.put("a", 1)
    assert c.get("a") is None
    assert len(c) == 0 and c.hits == c.misses == 0


def test_query_digest_is_byte_exact():
    a = np.arange(6, dtype=np.float32)
    b = a.copy()
    assert query_digest(a, None, None) == query_digest(b, None, None)
    b[0] = np.float32(-0.0)              # 0.0 vs -0.0: different bytes
    assert query_digest(a, None, None) != query_digest(b, None, None)
    assert (query_digest(a, None, None)
            != query_digest(a.astype(np.float64), None, None))
    assert (query_digest(None, a.astype(np.int32), None)
            != query_digest(a.astype(np.int32), None, None))


# ---------------------------------------------------------------------------
# exact result cache: bitwise hits
# ---------------------------------------------------------------------------

def test_exact_cache_hit_is_bitwise_all_methods(base_dir, small_corpus):
    caches = CacheHierarchy(exact_entries=256)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    for m in METHODS:
        cold = eng.process_batch(_reqs(small_corpus, m, range(4)))
        assert not any(r.cache_hit for r in cold)
        warm = eng.process_batch(_reqs(small_corpus, m, range(4)))
        assert all(r.cache_hit for r in warm)
        for c, w in zip(cold, warm):
            _assert_bitwise(c, w)
    assert caches.exact.hits >= 16


def test_exact_cache_respects_per_query_k_and_alpha(base_dir,
                                                    small_corpus):
    caches = CacheHierarchy(exact_entries=256)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    cold = eng.process_batch(_reqs(small_corpus, "hybrid", [0],
                                   alpha=0.3))
    # same query, different k or alpha: different key, no hit
    r_k = eng.process_batch(_reqs(small_corpus, "hybrid", [0], k=10,
                                  alpha=0.3))
    assert not r_k[0].cache_hit and len(r_k[0].pids) == 10
    r_a = eng.process_batch(_reqs(small_corpus, "hybrid", [0],
                                  alpha=0.7))
    assert not r_a[0].cache_hit
    # exact same request shape hits
    warm = eng.process_batch(_reqs(small_corpus, "hybrid", [0],
                                   alpha=0.3))
    assert warm[0].cache_hit
    _assert_bitwise(cold[0], warm[0])


def test_mixed_batch_partial_hits_bitwise(base_dir, small_corpus,
                                          reference):
    """A mixed-method batch with some queries warm and some cold: hits
    come from the cache, misses run the retriever, and every answer is
    bitwise the cache-free engine's answer."""
    caches = CacheHierarchy(exact_entries=256)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    # warm two of the four (one hybrid, one splade)
    eng.process_batch(_reqs(small_corpus, "hybrid", [0]))
    eng.process_batch(_reqs(small_corpus, "splade", [1]))

    reqs = (_reqs(small_corpus, "hybrid", [0, 2])
            + _reqs(small_corpus, "splade", [1, 3], qid0=2))
    got = eng.process_batch(reqs)
    assert got[0].cache_hit and got[2].cache_hit
    assert not got[1].cache_hit and not got[3].cache_hit

    ref = reference.process_batch(
        _reqs(small_corpus, "hybrid", [0, 2])
        + _reqs(small_corpus, "splade", [1, 3], qid0=2))
    for r, g in zip(ref, got):
        _assert_bitwise(r, g)


def test_exact_cache_eviction_at_capacity(base_dir, small_corpus):
    caches = CacheHierarchy(exact_entries=2)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    for i in range(3):
        eng.process_batch(_reqs(small_corpus, "splade", [i]))
    assert caches.exact.evictions >= 1
    # query 0 was evicted: runs cold again
    again = eng.process_batch(_reqs(small_corpus, "splade", [0]))
    assert not again[0].cache_hit


def test_generation_bump_invalidates_everything(base_dir, small_corpus):
    caches = CacheHierarchy(exact_entries=64, stage1_entries=64)
    retr = _fresh_retr(base_dir)
    eng = ServeEngine(retr, caches=caches)
    eng.process_batch(_reqs(small_corpus, "hybrid", range(3)))
    assert len(caches.exact) > 0 and len(caches.stage1) > 0
    gen = retr.bump_index_generation()
    assert gen == 1
    assert len(caches.exact) == 0 and len(caches.stage1) == 0
    assert caches.exact.invalidations > 0
    # post-bump runs miss, recompute, and re-fill under the new salt
    cold = eng.process_batch(_reqs(small_corpus, "hybrid", range(3)))
    assert not any(r.cache_hit for r in cold)
    warm = eng.process_batch(_reqs(small_corpus, "hybrid", range(3)))
    assert all(r.cache_hit for r in warm)


# ---------------------------------------------------------------------------
# stage-1 / candidate cache
# ---------------------------------------------------------------------------

def test_stage1_cache_splade_warms_hybrid(base_dir, small_corpus,
                                          reference):
    """Stage-1 entries are method-independent for splade-first plans: a
    splade batch warms the rows a later hybrid batch reuses — and the
    hybrid answer built from cached rows is bitwise the cold one."""
    caches = CacheHierarchy(stage1_entries=256)   # exact cache OFF
    retr = _fresh_retr(base_dir)
    eng = ServeEngine(retr, caches=caches)
    eng.process_batch(_reqs(small_corpus, "splade", range(4)))
    assert len(caches.stage1) == 4
    before = caches.stage1.hits
    got = eng.process_batch(_reqs(small_corpus, "hybrid", range(4)))
    assert caches.stage1.hits >= before + 4
    assert not any(r.cache_hit for r in got)      # full plan still ran
    ref = reference.process_batch(_reqs(small_corpus, "hybrid",
                                        range(4)))
    for r, g in zip(ref, got):
        _assert_bitwise(r, g)


def test_stage1_cache_colbert_candidates(base_dir, small_corpus,
                                         reference):
    caches = CacheHierarchy(stage1_entries=256)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    cold = eng.process_batch(_reqs(small_corpus, "colbert", range(4)))
    before = caches.stage1.hits
    warm = eng.process_batch(_reqs(small_corpus, "colbert", range(4)))
    assert caches.stage1.hits > before
    ref = reference.process_batch(_reqs(small_corpus, "colbert",
                                        range(4)))
    for a, b, c in zip(ref, cold, warm):
        _assert_bitwise(a, b)
        _assert_bitwise(a, c)


# ---------------------------------------------------------------------------
# sharded parity (thread 1/2/4 shards + process workers)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def shard_groups(base_dir, small_corpus):
    n_docs = small_corpus["cfg"].n_docs
    out = {}
    for s in (1, 2, 4):
        group = split_index_tree(base_dir, s,
                                 group_dir=base_dir / f"shards{s}")
        out[s] = build_sharded_retriever(
            [group / str(i) for i in range(s)],
            shard_boundaries(n_docs, s), mode="mmap",
            plaid_params=PLAID, multistage_params=MS)
    return out


@pytest.mark.parametrize("n_shards", [1, 2, 4])
@pytest.mark.parametrize("method", ["splade", "rerank", "hybrid"])
def test_sharded_cache_parity(base_dir, small_corpus, reference,
                              shard_groups, n_shards, method):
    caches = CacheHierarchy(exact_entries=128, stage1_entries=128)
    eng = ServeEngine(shard_groups[n_shards], caches=caches)
    ref = reference.process_batch(_reqs(small_corpus, method, range(4)))
    cold = eng.process_batch(_reqs(small_corpus, method, range(4)))
    warm = eng.process_batch(_reqs(small_corpus, method, range(4)))
    assert all(r.cache_hit for r in warm)
    for a, b, c in zip(ref, cold, warm):
        np.testing.assert_array_equal(np.asarray(a.pids),
                                      np.asarray(b.pids))
        np.testing.assert_allclose(np.asarray(a.scores),
                                   np.asarray(b.scores),
                                   rtol=1e-5, atol=1e-5)
        _assert_bitwise(b, c)            # hit vs cold: bitwise


def test_sharded_stage1_group_cache(base_dir, small_corpus,
                                    shard_groups):
    """Group-level stage-1 cache (2 shards, exact cache off): the
    second identical batch skips the per-shard stage-1 fanout and
    still produces bitwise the same answer."""
    caches = CacheHierarchy(stage1_entries=128)
    retr = shard_groups[2]
    eng = ServeEngine(retr, caches=caches)
    try:
        cold = eng.process_batch(_reqs(small_corpus, "hybrid",
                                       range(4)))
        assert len(caches.stage1) == 4
        before = caches.stage1.hits
        warm = eng.process_batch(_reqs(small_corpus, "hybrid",
                                       range(4)))
        assert caches.stage1.hits >= before + 4
        for c, w in zip(cold, warm):
            _assert_bitwise(c, w)
    finally:
        retr.attach_caches(None)


def test_process_group_cache_parity(base_dir, small_corpus,
                                    shard_groups):
    dirs, bounds = load_group(base_dir / "shards2")
    g = build_shard_group(dirs, bounds, workers="process", mode="mmap",
                          plaid_params=PLAID, multistage_params=MS)
    try:
        caches = CacheHierarchy(exact_entries=64, stage1_entries=64)
        eng = ServeEngine(g, caches=caches)
        cold = eng.process_batch(_reqs(small_corpus, "hybrid",
                                       range(4)))
        warm = eng.process_batch(_reqs(small_corpus, "hybrid",
                                       range(4)))
        assert all(r.cache_hit for r in warm)
        for c, w in zip(cold, warm):
            _assert_bitwise(c, w)
        # stage-1 rows were stored at the group (merged-row) level
        assert len(caches.stage1) == 4
    finally:
        g.close()


# ---------------------------------------------------------------------------
# SLO-aware admission
# ---------------------------------------------------------------------------

def _snap(stage1_ms, tail_ms, dispatches=10):
    return {"splade_stage1": {"ewma_ms": stage1_ms,
                              "dispatches": dispatches},
            "device_score:maxsim": {"ewma_ms": tail_ms,
                                    "dispatches": dispatches}}


def test_admission_ladder_unit():
    ac = AdmissionController(latency_slo_ms=100.0, shed_factor=3.0)
    # cold start admits full
    assert ac.decide("hybrid", True, {}).admission == ADMIT_FULL
    assert ac.decide("hybrid", True, {}).reason == "cold_start"
    # comfortably inside SLO
    assert ac.decide("hybrid", True,
                     _snap(10, 20)).admission == ADMIT_FULL
    # tail blows SLO, stage-1 fits → degrade with reason
    d = ac.decide("hybrid", True, _snap(10, 500))
    assert d.admission == ADMIT_DEGRADED and d.reason == "slo_tail"
    # both over, cheap within shed_factor× → still degrade
    d = ac.decide("hybrid", True, _snap(150, 500))
    assert d.admission == ADMIT_DEGRADED and d.reason == "slo_overload"
    # not degradable, full within shed_factor× → best-effort full
    d = ac.decide("colbert", False, _snap(10, 200))
    assert d.admission == ADMIT_FULL and d.reason == "slo_best_effort"
    # hopeless → shed
    d = ac.decide("hybrid", True, _snap(5000, 5000))
    assert d.admission == ADMIT_SHED and d.reason == "overload"
    # splade requests are costed at stage-1 only
    assert ac.decide("splade", False,
                     _snap(50, 9000)).admission == ADMIT_FULL
    # a tight per-request deadline sheds with reason "deadline"
    d = ac.decide("hybrid", True, _snap(50, 60), deadline_ms=1.0)
    assert d.admission == ADMIT_SHED and d.reason == "deadline"
    s = ac.stats()
    assert s["full_admits"] + s["degraded_admits"] + s["sheds"] == 9


def _poison(retr, stage1_s, tail_s):
    for _ in range(4):                   # drive the EWMA, not one sample
        retr.pipeline_stats.record("splade_stage1", wall_s=stage1_s)
        retr.pipeline_stats.record("device_score:maxsim", wall_s=tail_s)


def test_admission_degrades_hybrid_to_splade(base_dir, small_corpus,
                                             reference):
    """A stalled rerank tail (poisoned EWMA) degrades hybrid requests
    to the splade-only plan: the answer matches splade bitwise and
    carries degraded=True with the SLO reason code."""
    retr = _fresh_retr(base_dir)
    eng = ServeEngine(retr)
    srv = RetrievalServer(eng, n_threads=1,
                          admission=AdmissionController(50.0))
    srv.start()
    try:
        _poison(retr, stage1_s=0.001, tail_s=10.0)
        res = srv.submit(_reqs(small_corpus, "hybrid", [7])[0]) \
                 .result(timeout=60)
        assert res.degraded and res.degrade_reason == "slo_tail"
        ref = reference.process(_reqs(small_corpus, "splade", [7])[0])
        _assert_bitwise(ref, res)
        h = srv.health()
        assert h["admission"]["degraded_admits"] == 1
    finally:
        srv.stop()


def test_admission_sheds_before_queueing(base_dir, small_corpus):
    retr = _fresh_retr(base_dir)
    eng = ServeEngine(retr)
    srv = RetrievalServer(eng, n_threads=1,
                          admission=AdmissionController(50.0))
    srv.start()
    try:
        _poison(retr, stage1_s=10.0, tail_s=10.0)   # even splade hopeless
        fut = srv.submit(_reqs(small_corpus, "hybrid", [3])[0])
        with pytest.raises(RequestShed) as ei:
            fut.result(timeout=10)
        assert ei.value.reason == "overload"
        h = srv.health()
        assert h["sheds"] == 1 and h["served"] == 0
        assert h["admission"]["sheds"] == 1
    finally:
        srv.stop()


def test_shed_counted_separately_by_loadgen(base_dir, small_corpus):
    retr = _fresh_retr(base_dir)
    srv = RetrievalServer(ServeEngine(retr), n_threads=1,
                          admission=AdmissionController(50.0))
    srv.start()
    try:
        _poison(retr, stage1_s=10.0, tail_s=10.0)
        reqs = _reqs(small_corpus, "colbert", range(6))
        res = run_poisson_load(srv, reqs, qps=500.0, seed=0)
        assert res.shed == 6 and res.failed == 0
        assert len(res.latencies) == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# loadgen realism: Zipf skew, trace replay, outcome counters
# ---------------------------------------------------------------------------

def test_zipf_trace_skews_and_uniform_degenerates():
    t = zipf_trace(4000, 50, skew=1.3, seed=7)
    assert t.min() >= 0 and t.max() < 50
    counts = np.bincount(t, minlength=50)
    # heavy head: the most popular query dwarfs the uniform share
    assert counts.max() > 4 * (4000 / 50)
    u = zipf_trace(4000, 50, skew=0.0, seed=7)
    uc = np.bincount(u, minlength=50)
    assert uc.max() < 3 * (4000 / 50)
    # determinism
    np.testing.assert_array_equal(t, zipf_trace(4000, 50, skew=1.3,
                                                seed=7))


def test_load_trace_parses_and_rejects_empty(tmp_path):
    p = tmp_path / "trace.txt"
    p.write_text("# comment\n3\n1\n\n2  # inline\n")
    np.testing.assert_array_equal(load_trace(p), [3, 1, 2])
    empty = tmp_path / "empty.txt"
    empty.write_text("# nothing\n")
    with pytest.raises(ValueError):
        load_trace(empty)


def test_loadgen_counts_cache_hits_and_trace_mix(base_dir,
                                                 small_corpus):
    caches = CacheHierarchy(exact_entries=64)
    eng = ServeEngine(_fresh_retr(base_dir), caches=caches)
    srv = RetrievalServer(eng, n_threads=1)
    srv.start()
    try:
        eng.process_batch(_reqs(small_corpus, "splade", [0, 1, 2]))
        trace = [0, 1, 0, 1, 0, 2]       # 3 unique, 3 repeats
        reqs = []
        for j, q in enumerate(trace):
            r = _reqs(small_corpus, "splade", [q], qid0=j)[0]
            r.trace_id = q
            reqs.append(r)
        res = run_poisson_load(srv, reqs, qps=2000.0, seed=0)
        assert res.unique_queries == 3 and res.repeat_queries == 3
        assert res.cache_hits == 6       # cache pre-warmed: every hit
        s = res.summary()
        assert s["cache_hits"] == res.cache_hits
        assert s["shed"] == 0 and s["degraded"] == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# graded-relevance metric
# ---------------------------------------------------------------------------

def test_ndcg_at_k():
    ranked = np.array([[5, 3, 9], [1, 2, 3]])
    # binary: perfect first hit vs miss
    assert ndcg_at_k(ranked, [{5}, {7}], k=3) == pytest.approx(0.5)
    # graded: putting the high-gain doc first scores higher
    good = ndcg_at_k(np.array([[5, 3]]), [{5: 3.0, 3: 1.0}], k=2)
    bad = ndcg_at_k(np.array([[3, 5]]), [{5: 3.0, 3: 1.0}], k=2)
    assert good == pytest.approx(1.0) and bad < good
    # empty relevance contributes zero, not NaN
    assert ndcg_at_k(ranked, [set(), {1}], k=3) == pytest.approx(0.5)
