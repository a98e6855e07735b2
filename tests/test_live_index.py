"""Live (mutable) index: delta-segment parity, tombstone filtering,
compaction-vs-rebuild bitwise equality, cache invalidation, and the
mutation-RPC safety guards.

The central invariant (checked against a from-scratch rebuild oracle
with the serve index's geometry pinned): at any quiesce point, an
interleaved upsert/delete/query trace returns bitwise-identical top-k
to rebuilding the surviving corpus, under the monotone pid map
``sorted(survivors) <-> 0..n-1`` — across shard counts and worker
backends, before and after compaction.
"""

import threading

import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.sharded import (
    MUTATION_OPS,
    ProcessShardGroup,
    build_shard_group,
)
from repro.data.synth import SynthCfg, make_corpus
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.live import build_reference_indexes, map_global_to_ref
from repro.index.sharding import shard_boundaries, split_index_tree
from repro.index.splade_index import SpladeIndex, build_splade_index

# candidate_cap must not bind: the oracle rebuild changes stage-2
# candidate *sets* near the cap, so parity is only guaranteed when both
# sides keep every candidate
PLAID = PlaidParams(nprobe=4, candidate_cap=4096, ndocs=128, k=10)
MS = MultiStageParams(first_k=64, k=10)
METHODS = ("splade", "colbert", "rerank", "hybrid")
HOLD = 8          # held-out docs, upserted during the tests
DELETED = (5, 17, 100, 201)   # base pids tombstoned by mutate()


@pytest.fixture(scope="module")
def corpus():
    return make_corpus(SynthCfg(n_docs=240, n_queries=16, vocab=512,
                                dim=32, n_topics=12, doc_maxlen=20,
                                query_maxlen=6, seed=3))


def _base_n(corpus):
    return corpus["cfg"].n_docs - HOLD


@pytest.fixture(scope="module")
def base_dir(tmp_path_factory, corpus):
    base = tmp_path_factory.mktemp("live_base")
    n = _base_n(corpus)
    build_colbert_index(base / "colbert", corpus["doc_embs"][:n],
                        corpus["doc_lens"][:n], nbits=4, n_centroids=64,
                        kmeans_iters=4)
    build_splade_index(corpus["doc_term_ids"][:n],
                       corpus["doc_term_weights"][:n],
                       corpus["cfg"].vocab, n).save(base / "splade")
    return base


def _queries(corpus):
    return dict(q_embs=list(corpus["q_embs"]),
                term_ids=list(corpus["q_term_ids"]),
                term_weights=list(corpus["q_term_weights"]))


def _make_unsharded(base_dir):
    return MultiStageRetriever(
        SpladeIndex.load(base_dir / "splade", mmap=True),
        PLAIDSearcher(ColBERTIndex(base_dir / "colbert"), PLAID), MS)


def _mutate(retr, corpus):
    """The canonical trace: upsert the held-out docs, tombstone a few
    base docs and one delta doc. Returns the full deleted set."""
    n = _base_n(corpus)
    new_pids = [retr.live_upsert(corpus["doc_embs"][j],
                                 corpus["doc_term_ids"][j],
                                 corpus["doc_term_weights"][j],
                                 corpus["doc_lens"][j])
                for j in range(n, corpus["cfg"].n_docs)]
    assert new_pids == list(range(n, n + HOLD))   # append-only global pids
    deleted = list(DELETED) + [new_pids[2]]
    for g in deleted:
        assert retr.live_delete(g)
    return deleted


@pytest.fixture(scope="module")
def oracle(tmp_path_factory, corpus, base_dir):
    """From-scratch rebuild of the canonical trace's surviving corpus,
    with the base index's frozen geometry pinned."""
    deleted = set(DELETED) | {_base_n(corpus) + 2}
    survivors = np.array([g for g in range(corpus["cfg"].n_docs)
                          if g not in deleted], np.int64)
    idx = ColBERTIndex(base_dir / "colbert")
    rd = tmp_path_factory.mktemp("live_oracle")
    build_reference_indexes(
        rd / "colbert", rd / "splade",
        corpus["doc_embs"][survivors], corpus["doc_lens"][survivors],
        corpus["doc_term_ids"][survivors],
        corpus["doc_term_weights"][survivors], corpus["cfg"].vocab,
        centroids=idx.centroids, bucket_cutoffs=idx.bucket_cutoffs,
        bucket_weights=idx.bucket_weights, nbits=idx.nbits,
        quantum=SpladeIndex.load(base_dir / "splade").quantum)
    ref = _make_unsharded(rd)
    q = _queries(corpus)
    expected = {m: ref.search_batch(m, **q, k=10) for m in METHODS}
    return survivors, expected


def _assert_parity(retr, corpus, oracle, tag=""):
    survivors, expected = oracle
    q = _queries(corpus)
    for m in METHODS:
        lp, ls = retr.search_batch(m, **q, k=10)
        rp, rs = expected[m]
        np.testing.assert_array_equal(map_global_to_ref(lp, survivors),
                                      rp, err_msg=f"{tag} {m} pids")
        np.testing.assert_array_equal(ls, rs, err_msg=f"{tag} {m} scores")


# ---------------------------------------------------------------------------
# delta codec
# ---------------------------------------------------------------------------

def test_delta_encode_matches_builder(corpus, base_dir):
    """encode_doc quantises a document bitwise as the from-scratch
    builder does (per-row deterministic assign + encode)."""
    retr = _make_unsharded(base_dir)
    live = retr.enable_live()
    idx = retr.searcher.index
    for pid in (0, 7, 100):
        cids, packed, L = live.encode_doc(corpus["doc_embs"][pid],
                                          corpus["doc_lens"][pid])
        lo, hi = idx.doc_offsets[pid], idx.doc_offsets[pid + 1]
        assert L == idx.doclens[pid] == hi - lo
        np.testing.assert_array_equal(cids,
                                      np.asarray(idx.store.codes[lo:hi]))
        np.testing.assert_array_equal(
            packed, np.asarray(idx.store.residuals[lo:hi]))
    with pytest.raises(ValueError):
        live.encode_doc(corpus["doc_embs"][0][:, :-1])   # wrong dim
    with pytest.raises(ValueError):
        live.encode_doc(corpus["doc_embs"][0], 0)        # empty doc


def test_padded_encode_matches_per_length_in_one_program():
    """The upsert encoder pads a passage to ``doc_maxlen`` and encodes it
    in one program for every length: bitwise the codes of encoding the
    passage at its own length, and no compile after the first length."""
    import types

    import jax.numpy as jnp

    from repro.index import kmeans, residual
    from repro.index.live import LiveIndexState, encode_padded
    from repro.serving.pipeline import PipelineStats

    rng = np.random.default_rng(11)

    def unit(shape):
        x = rng.standard_normal(shape).astype(np.float32)
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    # a geometry no other test compiles the encoder for
    dim, maxlen, nbits = 24, 37, 2
    index = types.SimpleNamespace(
        n_docs=5, doc_maxlen=maxlen, dim=dim, nbits=nbits,
        store=types.SimpleNamespace(packed_dim=dim * nbits // 8),
        n_centroids=19, centroids=unit((19, dim)),
        bucket_cutoffs=np.array([-0.1, 0.0, 0.1], np.float32),
        bucket_weights=np.array([-0.15, -0.05, 0.05, 0.15], np.float32))
    live = LiveIndexState(index, types.SimpleNamespace(quantum=0.01,
                                                       vocab=64))
    embs = [unit((L, dim)) for L in (1, 13, maxlen)]
    before = encode_padded._cache_size()
    encoded = [live.encode_doc(embs[0])]
    compiles = PipelineStats()        # counts from the second length on
    encoded += [live.encode_doc(emb) for emb in embs[1:]]
    assert encode_padded._cache_size() == before + 1
    assert compiles.snapshot()["counters"].get("jax_compiles", 0) == 0
    for emb, (cids, packed, n) in zip(embs, encoded):
        L = len(emb)
        ref_cids = np.asarray(kmeans.assign(jnp.asarray(emb),
                                            jnp.asarray(index.centroids))[0])
        ref_packed = np.asarray(residual.encode_residuals(
            jnp.asarray(emb), jnp.asarray(ref_cids),
            jnp.asarray(index.centroids),
            jnp.asarray(index.bucket_cutoffs), nbits))
        assert n == L and cids.dtype == np.int32 and packed.dtype == np.uint8
        np.testing.assert_array_equal(cids, ref_cids)
        np.testing.assert_array_equal(packed, ref_packed)


def test_clean_live_serves_frozen_results(corpus, base_dir):
    retr = _make_unsharded(base_dir)
    q = _queries(corpus)
    before = {m: retr.search_batch(m, **q, k=10) for m in METHODS}
    retr.enable_live()
    assert not retr.live.dirty and retr.index_generation == 0
    for m in METHODS:
        p, s = retr.search_batch(m, **q, k=10)
        np.testing.assert_array_equal(before[m][0], p)
        np.testing.assert_array_equal(before[m][1], s)


# ---------------------------------------------------------------------------
# tombstones
# ---------------------------------------------------------------------------

def test_tombstone_filtered_and_backfilled(corpus, base_dir):
    """A deleted doc vanishes from every method's top-k, and the slot is
    backfilled (k stays full — shard-side pre-top-k exclusion, so the
    (k+1)-th doc takes its place rather than leaving a hole)."""
    retr = _make_unsharded(base_dir)
    retr.enable_live()
    q = _queries(corpus)
    p0, _ = retr.search_batch("splade", **q, k=10)
    victim = int(p0[0, 0])
    assert retr.live_delete(victim)
    assert not retr.live_delete(victim)          # double delete: no-op
    assert not retr.live_delete(10 ** 9)         # unknown pid
    for m in METHODS:
        p, s = retr.search_batch(m, **q, k=10)
        assert victim not in p
        assert (p >= 0).all() and np.isfinite(np.asarray(s)).all()


def test_deleted_doc_cached_stage1_is_not_served(corpus, base_dir):
    """Generation-salted caches: a doc cached by the stage-1/exact
    caches must not survive its own deletion (the mutation bumps the
    index generation, which invalidates every cache key)."""
    from repro.serving.context import CacheHierarchy
    from repro.serving.engine import Request, ServeEngine

    retr = _make_unsharded(base_dir)
    retr.enable_live()
    engine = ServeEngine(retr, caches=CacheHierarchy(exact_entries=64,
                                                     stage1_entries=64))
    req = lambda qid: Request(qid=qid, method="hybrid",
                              q_emb=corpus["q_embs"][0],
                              term_ids=corpus["q_term_ids"][0],
                              term_weights=corpus["q_term_weights"][0],
                              k=5)
    r0 = engine.process(req(0))
    r1 = engine.process(req(1))                  # warm: exact-cache hit
    assert r1.cache_hit and list(r1.pids) == list(r0.pids)
    victim = int(r0.pids[0])
    assert engine.live_delete(victim)
    r2 = engine.process(req(2))
    assert not r2.cache_hit                      # generation bump missed
    assert victim not in list(r2.pids)
    assert len(r2.pids) == 5


# ---------------------------------------------------------------------------
# rebuild parity (the correctness bar)
# ---------------------------------------------------------------------------

def test_unsharded_parity_and_compaction(corpus, base_dir, oracle):
    retr = _make_unsharded(base_dir)
    retr.enable_live()
    _mutate(retr, corpus)
    _assert_parity(retr, corpus, oracle, "dirty")

    gen = retr.index_generation
    out = retr.compact_live()
    assert out["compacted"] == HOLD
    assert retr.compact_live() is None           # nothing left to merge
    assert retr.index_generation > gen           # caches invalidated
    assert retr.live.n_delta == 0
    st = retr.live_stats()
    assert st["compactions"] == 1 and st["docs_compacted"] == HOLD
    assert st["tombstones"] == len(DELETED) + 1
    _assert_parity(retr, corpus, oracle, "compacted")
    # the swapped-in layout grew by the delta, pids unchanged
    assert retr.searcher.index.n_docs == corpus["cfg"].n_docs


@pytest.mark.parametrize("n_shards", [1, 2, 4])
def test_thread_group_parity_and_compaction(corpus, base_dir, oracle,
                                            n_shards):
    group_dir = split_index_tree(base_dir, n_shards,
                                 group_dir=base_dir / f"sh{n_shards}")
    g = build_shard_group(
        [group_dir / str(i) for i in range(n_shards)],
        shard_boundaries(_base_n(corpus), n_shards), workers="thread",
        plaid_params=PLAID, multistage_params=MS)
    g.enable_live()
    _mutate(g, corpus)
    _assert_parity(g, corpus, oracle, f"thread x{n_shards} dirty")
    assert g.compact_live()["compacted"] == HOLD
    assert g.live.n_delta == 0 and g.index_generation > 0
    _assert_parity(g, corpus, oracle, f"thread x{n_shards} compacted")
    assert g.n_docs == corpus["cfg"].n_docs      # boundary grew


def test_process_group_parity_and_compaction(corpus, base_dir, oracle):
    group_dir = split_index_tree(base_dir, 2, group_dir=base_dir / "sh2")
    g = build_shard_group([group_dir / str(i) for i in range(2)],
                          shard_boundaries(_base_n(corpus), 2),
                          workers="process", plaid_params=PLAID,
                          multistage_params=MS)
    try:
        g.enable_live()
        _mutate(g, corpus)
        _assert_parity(g, corpus, oracle, "process x2 dirty")
        assert g.compact_live()["compacted"] == HOLD
        _assert_parity(g, corpus, oracle, "process x2 compacted")
        # mutations are replicated as writes, never as hedged/failover
        # retries
        counters = g.pipeline_stats.snapshot().get("counters", {})
        assert counters.get("hedges", 0) == 0
        assert counters.get("failover_retries", 0) == 0
        h = g.worker_health()
        assert any("live" in w for w in h)
    finally:
        g.close()


def test_query_during_compaction(corpus, base_dir, oracle):
    """Readers and the compaction swap interleave safely: queries keep
    returning the (identical) answer while the generation swap happens
    under the write gate."""
    group_dir = split_index_tree(base_dir, 2, group_dir=base_dir / "sh2")
    g = build_shard_group([group_dir / str(i) for i in range(2)],
                          shard_boundaries(_base_n(corpus), 2),
                          workers="thread", plaid_params=PLAID,
                          multistage_params=MS)
    g.enable_live()
    _mutate(g, corpus)
    q = _queries(corpus)
    expect_p, expect_s = g.search_batch("hybrid", **q, k=10)
    errors, stop = [], threading.Event()

    def reader():
        while not stop.is_set():
            try:
                p, s = g.search_batch("hybrid", **q, k=10)
                np.testing.assert_array_equal(p, expect_p)
                np.testing.assert_array_equal(s, expect_s)
            except Exception as e:   # pragma: no cover - surfaced below
                errors.append(e)
                return

    threads = [threading.Thread(target=reader) for _ in range(3)]
    for t in threads:
        t.start()
    try:
        assert g.compact_live()["compacted"] == HOLD
        p, s = g.search_batch("hybrid", **q, k=10)
        np.testing.assert_array_equal(p, expect_p)
        np.testing.assert_array_equal(s, expect_s)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=30)
    assert not errors, errors[0]
    _assert_parity(g, corpus, oracle, "compacted under readers")


def test_auto_compactor_stop_returns_and_the_thread_ends(base_dir):
    """``stop()`` returns once the compactor's thread has ended, whether
    it is called once or again after the end."""
    from repro.index.live import AutoCompactor
    retr = _make_unsharded(base_dir)
    retr.enable_live()
    c = AutoCompactor(retr, every=10**6, interval_s=0.01)
    c.start()
    c.stop()
    assert not c.is_alive()
    c.stop()
    assert not c.is_alive()


# ---------------------------------------------------------------------------
# mutation RPCs are not hedged / not retried on siblings
# ---------------------------------------------------------------------------

class _FakeRep:
    class event:
        @staticmethod
        def is_set():
            return False


class _FakeCli:
    def __init__(self):
        self.wait_kwargs = None

    def wait(self, rep, **kw):
        self.wait_kwargs = kw
        return {"ok": True}


class _FakeReplicaSet:
    total = 2

    def __init__(self):
        self.budget_calls = 0

    def hedge_budget_ms(self, r):
        self.budget_calls += 1
        return 1.0       # would hedge almost immediately if armed

    def record_success(self, r, ms):
        pass

    def acquire(self, exclude=None):   # pragma: no cover - must not run
        raise AssertionError("mutation op acquired a sibling replica")


def _fake_group():
    g = ProcessShardGroup.__new__(ProcessShardGroup)
    g._replica_sets = [_FakeReplicaSet()]
    return g


def test_mutation_ops_never_arm_hedge_budget():
    from repro.core.sharded import _Slot

    g = _fake_group()
    for op in sorted(MUTATION_OPS):
        slot = _Slot(op, {})
        slot.cli, slot.rep, slot.replica = _FakeCli(), _FakeRep(), 0
        out = g._wait_replica(0, slot)
        assert out == {"ok": True}
        # waited without a hedge timeout: the budget was never consulted
        assert g._replica_sets[0].budget_calls == 0
        assert slot.cli.wait_kwargs == {}
    # a pure op on the same group DOES arm the budget
    slot = _Slot("splade", {})
    slot.cli, slot.rep, slot.replica = _FakeCli(), _FakeRep(), 0
    g._wait_replica(0, slot)
    assert g._replica_sets[0].budget_calls == 1
    assert slot.cli.wait_kwargs.get("timeout") is not None


def test_mutation_ops_never_resent_on_siblings():
    from repro.serving.transport import ShardWorkerDied

    from repro.core.sharded import _Slot

    g = _fake_group()
    for op in sorted(MUTATION_OPS):
        slot = _Slot(op, {})
        with pytest.raises(ShardWorkerDied, match="not retryable"):
            g._resend_slot(0, slot)
        boom = RuntimeError("original failure")
        with pytest.raises(RuntimeError, match="original failure"):
            g._resend_slot(0, slot, last_error=boom)
