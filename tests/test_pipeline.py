"""Stage-graph pipeline executor: plan compilation, depth>1 parity with
the synchronous path (all four methods + mixed batches), backpressure,
instrumentation (merged stage stats + AccessStats, overlap fraction),
and clean shutdown."""

import threading
import time

import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.store import AccessStats
from repro.index.builder import ColBERTIndex
from repro.index.splade_index import build_splade_index
from repro.serving.engine import Request, ServeEngine
from repro.serving.pipeline import (
    DEVICE,
    HOST,
    CandidateBatch,
    PipelineExecutor,
    PipelineStopped,
    Stage,
    StagePlan,
)
from repro.serving.server import RetrievalServer

METHODS = ("colbert", "splade", "rerank", "hybrid")


@pytest.fixture(scope="module")
def stack(built_index, small_corpus):
    index = ColBERTIndex(built_index, mode="mmap")
    searcher = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                                ndocs=128, k=50))
    sidx = build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)
    retr = MultiStageRetriever(sidx, searcher,
                               MultiStageParams(first_k=50, k=20))
    return index, searcher, retr


def _requests(small_corpus, n, k=10, methods=METHODS):
    return [Request(qid=i, method=methods[i % len(methods)],
                    q_emb=small_corpus["q_embs"][i],
                    term_ids=small_corpus["q_term_ids"][i],
                    term_weights=small_corpus["q_term_weights"][i], k=k)
            for i in range(n)]


# ---------------------------------------------------------------------------
# plan compilation
# ---------------------------------------------------------------------------

def test_plans_use_typed_stage_vocabulary(stack):
    _, _, retr = stack
    expect = {
        "colbert": ("plaid_probe", "host_gather:codes",
                    "device_score:approx", "host_gather:residuals",
                    "fused_rerank"),
        "splade": ("splade_stage1", "fuse_splade"),
        "rerank": ("splade_stage1", "host_gather:residuals",
                   "fused_rerank", "fused_rerank:sync"),
        "hybrid": ("splade_stage1", "host_gather:residuals",
                   "fused_rerank", "fused_rerank:sync"),
    }
    for method, names in expect.items():
        plan = retr.compile_plan(method)
        assert plan.stage_names() == names
        # mmap store: gathers are host-bound, scoring device-bound
        kinds = {s.name: s.kind for s in plan.stages}
        for name in names:
            if name.startswith("host_gather"):
                assert kinds[name] == HOST
            if name.startswith(("device_score", "plaid_probe",
                                "fused_rerank")):
                assert kinds[name] == DEVICE
    with pytest.raises(ValueError):
        retr.compile_plan("no-such-method")


def test_split_backend_keeps_legacy_stage_vocabulary(stack):
    _, _, retr = stack
    expect = {
        "colbert": ("plaid_probe", "host_gather:codes",
                    "device_score:approx", "host_gather:residuals",
                    "device_score:exact", "fuse_topk"),
        "rerank": ("splade_stage1", "host_gather:residuals",
                   "device_score:maxsim", "fuse_topk"),
        "hybrid": ("splade_stage1", "host_gather:residuals",
                   "device_score:maxsim", "fuse_topk"),
    }
    retr.set_rerank_backend("split")
    try:
        for method, names in expect.items():
            assert retr.compile_plan(method).stage_names() == names
        with pytest.raises(ValueError):
            retr.set_rerank_backend("no-such-backend")
    finally:
        retr.set_rerank_backend(retr.params.rerank_backend)


def test_plans_cached_per_method_and_backend(stack):
    _, _, retr = stack
    assert retr.compile_plan("hybrid") is retr.compile_plan("hybrid")
    assert retr.compile_plan("hybrid") is not retr.compile_plan("rerank")


# ---------------------------------------------------------------------------
# parity: pipelined execution == synchronous plan run == search_batch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("method", METHODS)
@pytest.mark.parametrize("depth,workers", [(2, "single"), (3, "single"),
                                           (2, "kind")])
def test_executor_parity_with_sync(stack, small_corpus, method, depth,
                                   workers):
    _, _, retr = stack
    B, n_batches = 4, 3
    plan = retr.compile_plan(method)

    def batch(bi):
        idx = [(bi * B + j) % 40 for j in range(B)]
        return retr.build_batch(
            method,
            q_embs=[small_corpus["q_embs"][i] for i in idx],
            term_ids=[small_corpus["q_term_ids"][i] for i in idx],
            term_weights=[small_corpus["q_term_weights"][i] for i in idx],
            alphas=retr._alpha_array(None, B), k=15)

    sync = [plan.run(batch(bi)) for bi in range(n_batches)]
    px = PipelineExecutor(plan, depth=depth, stats=retr.pipeline_stats,
                          workers=workers)
    try:
        futs = [px.submit(batch(bi)) for bi in range(n_batches)]
        piped = [f.result(timeout=120) for f in futs]
    finally:
        px.stop()
    for s, p in zip(sync, piped):
        np.testing.assert_array_equal(s.pids, p.pids)
        np.testing.assert_allclose(s.scores, p.scores, rtol=1e-5,
                                   atol=1e-5)


def test_server_pipelined_equals_sequential_mixed(stack, small_corpus):
    """Depth-2 pipelined serving of mixed-method micro-batches returns
    exactly what the synchronous server returns."""
    _, _, retr = stack
    n = 16
    seq_srv = RetrievalServer(ServeEngine(retr), n_threads=1)
    seq_srv.start()
    seq = [seq_srv.submit(r).result(timeout=60)
           for r in _requests(small_corpus, n)]
    seq_srv.stop()

    srv = RetrievalServer(ServeEngine(retr, pipeline_depth=2),
                          n_threads=1, max_batch=4, batch_timeout_ms=25)
    # queued before start(): four mixed batches of four
    futs = [srv.submit(r) for r in _requests(small_corpus, n)]
    srv.start()
    piped = [f.result(timeout=60) for f in futs]
    assert srv.health()["served"] == n
    srv.stop()

    for r_seq, r_pipe in zip(seq, piped):
        assert r_seq.qid == r_pipe.qid
        np.testing.assert_array_equal(r_seq.pids, r_pipe.pids)
        np.testing.assert_allclose(r_seq.scores, r_pipe.scores,
                                   rtol=1e-4, atol=1e-4)


def test_pipelined_respects_per_request_k_and_alpha(stack, small_corpus):
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr, pipeline_depth=2),
                          n_threads=1, max_batch=4, batch_timeout_ms=25)
    reqs = _requests(small_corpus, 4, methods=("hybrid",))
    for r, want in zip(reqs, (3, 10, 7, 1)):
        r.k = want
    reqs[1].alpha = 0.9
    futs = [srv.submit(r) for r in reqs]     # one batch: queued first
    srv.start()
    for r, fut in zip(reqs, futs):
        assert len(fut.result(timeout=60).pids) == r.k
    expect = retr.search("hybrid", q_emb=reqs[1].q_emb,
                         term_ids=reqs[1].term_ids,
                         term_weights=reqs[1].term_weights,
                         alpha=0.9, k=10)[0]
    np.testing.assert_array_equal(futs[1].result().pids, expect)
    srv.stop()


def test_pipelined_isolates_poisoned_request(stack, small_corpus):
    """One bad request in a pipelined batch fails alone; co-batched
    neighbours are retried and still succeed."""
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr, pipeline_depth=2),
                          n_threads=1, max_batch=4, batch_timeout_ms=25)
    reqs = _requests(small_corpus, 4)
    reqs[2].method = "no-such-method"
    futs = [srv.submit(r) for r in reqs]     # one batch: queued first
    srv.start()
    with pytest.raises(ValueError):
        futs[2].result(timeout=60)
    for i in (0, 1, 3):
        assert len(futs[i].result(timeout=60).pids) > 0
    srv.stop()


# ---------------------------------------------------------------------------
# backpressure + shutdown (on a synthetic plan so timing is controlled)
# ---------------------------------------------------------------------------

def _slow_plan(delay_a=0.0, delay_b=0.0):
    def a(cb):
        time.sleep(delay_a)
        return cb.with_state(a_done=True)

    def b(cb):
        time.sleep(delay_b)
        return cb.with_state(b_done=True)

    return StagePlan(method="slow", stages=(Stage("host_gather", HOST, a),
                                            Stage("device_score", DEVICE,
                                                  b)))


def _cb(i=0):
    return CandidateBatch(method="slow", k=1,
                          term_ids=(np.asarray([i]),))


def test_bounded_pipeline_backpressures_producer():
    """depth bounds the batches in flight: with depth=1 every submit
    after the first blocks until the previous batch clears the whole
    pipeline — producers are backpressured, memory stays bounded."""
    delay = 0.05
    px = PipelineExecutor(_slow_plan(delay_a=delay, delay_b=delay),
                          depth=1)
    try:
        t0 = time.perf_counter()
        futs = [px.submit(_cb(i)) for i in range(4)]
        submit_wall = time.perf_counter() - t0
        # submits 2..4 each wait one full pipeline traversal (2 stages)
        assert submit_wall >= 3 * 2 * delay * 0.8, submit_wall
        assert sum(px.queue_depths().values()) <= 1
        for f in futs:
            assert f.result(timeout=30).state["b_done"]
    finally:
        px.stop()


def test_executor_room_decides_the_engines_admission(stack, small_corpus):
    """``has_room``: fewer than ``depth`` batches admitted. A pipelined
    engine can admit a micro-batch while every executor it feeds has
    room; a synchronous engine always can."""
    _, _, retr = stack
    gate = threading.Event()

    def wait(cb):
        assert gate.wait(timeout=30)
        return cb

    px = PipelineExecutor(StagePlan(method="gated", stages=(
        Stage("host_gather", HOST, wait),)), depth=2)
    eng = ServeEngine(retr, pipeline_depth=2)
    eng._pipelines["hybrid"] = px
    hybrid, colbert = _requests(small_corpus, 2,
                                methods=("hybrid", "colbert"))
    try:
        futs = [px.submit(_cb(0))]
        assert px.has_room() and eng.can_admit([hybrid])
        futs.append(px.submit(_cb(1)))
        assert not px.has_room()
        assert not eng.can_admit([hybrid])
        assert not eng.can_admit([colbert, hybrid])
        assert eng.can_admit([colbert])     # its executor is not built
        assert ServeEngine(retr).can_admit([hybrid])
        gate.set()
        for f in futs:
            f.result(timeout=30)
        assert px.drain(timeout=30)
        assert px.has_room() and eng.can_admit([hybrid])
    finally:
        gate.set()
        eng.stop_pipelines()


def test_depth2_overlaps_two_stage_plan_threaded():
    """Threaded (kind-worker) mode, depth=2: GIL-releasing stages of
    consecutive batches run concurrently, so total wall for N batches
    approaches N+1 stage-times instead of 2N (serial)."""
    delay = 0.05
    n = 6
    px = PipelineExecutor(_slow_plan(delay_a=delay, delay_b=delay),
                          depth=2, workers="kind")
    try:
        t0 = time.perf_counter()
        futs = [px.submit(_cb(i)) for i in range(n)]
        for f in futs:
            f.result(timeout=30)
        wall = time.perf_counter() - t0
    finally:
        px.stop()
    serial = 2 * n * delay
    assert wall < serial * 0.85, (wall, serial)


def test_single_worker_parks_at_sync_for_lookahead():
    """Software pipelining: with stages marked opens_async/closes_async,
    the single worker runs batch N+1's pre-sync stages before batch N's
    sync stage, hiding the async device execution behind host work."""
    order = []

    def dispatch(cb):
        order.append(("dispatch", int(cb.term_ids[0][0])))
        return cb

    def sync(cb):
        order.append(("sync", int(cb.term_ids[0][0])))
        return cb.evolve(pids=np.zeros((1, 1), np.int64))

    plan = StagePlan(method="x", stages=(
        Stage("host_gather", HOST, dispatch, opens_async=True),
        Stage("fuse_topk", HOST, sync, closes_async=True)))
    px = PipelineExecutor(plan, depth=2, workers="single")
    try:
        futs = [px.submit(_cb(i)) for i in range(3)]
        for f in futs:
            f.result(timeout=30)
    finally:
        px.stop()
    # batch 1's dispatch must precede batch 0's sync (lookahead), and
    # every batch still runs dispatch before its own sync
    assert order.index(("dispatch", 1)) < order.index(("sync", 0)), order
    for i in range(3):
        assert order.index(("dispatch", i)) < order.index(("sync", i))


def test_stop_resolves_or_fails_inflight():
    """stop() with batches queued and mid-stage: every future completes
    promptly — finished batches resolve, the rest fail PipelineStopped;
    nothing hangs."""
    px = PipelineExecutor(_slow_plan(delay_a=0.15), depth=2)
    futs = [px.submit(_cb(i)) for i in range(3)]
    time.sleep(0.05)                   # first batch is mid-stage
    t0 = time.perf_counter()
    px.stop()
    assert time.perf_counter() - t0 < 5.0
    states = []
    for f in futs:
        assert f.done()
        states.append("ok" if f.exception() is None else "stopped")
        if f.exception() is not None:
            assert isinstance(f.exception(), PipelineStopped)
    assert "stopped" in states        # at least the queued ones failed
    with pytest.raises(PipelineStopped):
        px.submit(_cb())


def test_stage_exception_fails_only_that_batch():
    def boom(cb):
        if int(cb.term_ids[0][0]) == 1:
            raise RuntimeError("injected")
        return cb.evolve(pids=np.zeros((1, 1), np.int64))

    plan = StagePlan(method="boom", stages=(Stage("fuse_topk", HOST,
                                                  boom),))
    px = PipelineExecutor(plan, depth=2)
    try:
        futs = [px.submit(_cb(i)) for i in range(3)]
        with pytest.raises(RuntimeError, match="injected"):
            futs[1].result(timeout=10)
        assert futs[0].result(timeout=10).pids is not None
        assert futs[2].result(timeout=10).pids is not None
    finally:
        px.stop()


def test_stop_with_parked_async_window_does_not_corrupt_overlap():
    """A batch killed between its opens_async and closes_async stages
    must close its async window, or every later (even strictly serial)
    run on the shared stats would read as ~100% overlapped."""
    from repro.serving.pipeline import PipelineStats

    stats = PipelineStats()

    def dispatch(cb):
        return cb

    def sync(cb):
        time.sleep(0.2)                 # keep batch 2 parked behind it
        return cb.evolve(pids=np.zeros((1, 1), np.int64))

    plan = StagePlan(method="x", stages=(
        Stage("host_gather", HOST, dispatch, opens_async=True),
        Stage("fuse_topk", HOST, sync, closes_async=True)))
    px = PipelineExecutor(plan, depth=2, stats=stats, workers="single")
    futs = [px.submit(_cb(i)) for i in range(2)]
    time.sleep(0.05)                    # batch 1 parked, window open
    px.stop()
    for f in futs:
        assert f.done()
    # a purely serial run afterwards must not read as overlapped
    stats.reset()
    plan.run(_cb(0), stats=stats)
    assert stats.snapshot()["overlap_fraction"] == 0.0


def test_server_restart_over_same_engine(stack, small_corpus):
    """stop() must not wedge the caller-owned engine: a restarted (or
    new) server over the same pipelined engine keeps serving."""
    _, _, retr = stack
    eng = ServeEngine(retr, pipeline_depth=2)
    srv = RetrievalServer(eng, n_threads=1, max_batch=4,
                          batch_timeout_ms=10)
    srv.start()
    assert len(srv.submit(_requests(small_corpus, 1)[0])
               .result(timeout=60).pids) > 0
    srv.stop()
    srv2 = RetrievalServer(eng, n_threads=1, max_batch=4,
                           batch_timeout_ms=10)
    srv2.start()
    futs = [srv2.submit(r) for r in _requests(small_corpus, 4)]
    for f in futs:
        assert len(f.result(timeout=60).pids) > 0
    srv2.stop()


def test_server_stop_with_pipeline_fails_unserved(stack, small_corpus):
    """Server stop() under pipelining: no client future is left
    pending."""
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr, pipeline_depth=2), n_threads=1)
    # never started: nothing drains the queue
    futs = [srv.submit(r) for r in _requests(small_corpus, 3)]
    srv.stop()
    for fut in futs:
        assert fut.done()
        with pytest.raises(RuntimeError, match="server stopped"):
            fut.result(timeout=1)


# ---------------------------------------------------------------------------
# instrumentation: merged per-stage record + overlap fraction
# ---------------------------------------------------------------------------

def test_stage_records_merge_access_stats(stack, small_corpus):
    """The per-stage record folds mmap page/token accounting into the
    same structure as wall time — and only gather stages touch pages."""
    index, _, retr = stack
    index.store.stats.reset()
    retr.reset_stage_stats()
    B = 4
    retr.search_batch(
        "hybrid", k=10,
        q_embs=[small_corpus["q_embs"][i] for i in range(B)],
        term_ids=[small_corpus["q_term_ids"][i] for i in range(B)],
        term_weights=[small_corpus["q_term_weights"][i] for i in range(B)])
    snap = retr.pipeline_stats.snapshot()
    gather = snap["stages"]["host_gather:residuals"]
    assert gather["pages_touched"] > 0
    assert gather["h2d_bytes"] > 0
    assert gather["dispatches"] == 1 and gather["queries"] == B
    assert snap["stages"]["fused_rerank"]["pages_touched"] == 0
    assert snap["stages"]["fused_rerank"]["device_dispatches"] == 1
    assert snap["stages"]["splade_stage1"]["dispatches"] == 1
    # synchronous run: no two stages ever execute concurrently
    assert snap["overlap_fraction"] == 0.0


def test_pipelined_overlap_fraction_positive(stack, small_corpus):
    """Depth-2 execution must actually overlap stages across
    micro-batches (the whole point of the pipeline)."""
    _, _, retr = stack
    plan = retr.compile_plan("hybrid")
    mk = lambda bi: retr.build_batch(
        "hybrid",
        q_embs=[small_corpus["q_embs"][(bi + j) % 40] for j in range(4)],
        term_ids=[small_corpus["q_term_ids"][(bi + j) % 40]
                  for j in range(4)],
        term_weights=[small_corpus["q_term_weights"][(bi + j) % 40]
                      for j in range(4)],
        alphas=retr._alpha_array(None, 4), k=10)
    plan.run(mk(0))                   # warm compiled shapes
    retr.reset_stage_stats()
    px = PipelineExecutor(plan, depth=2, stats=retr.pipeline_stats)
    try:
        futs = [px.submit(mk(bi)) for bi in range(8)]
        for f in futs:
            f.result(timeout=120)
    finally:
        px.stop()
    snap = retr.pipeline_stats.snapshot()
    assert 0.0 < snap["overlap_fraction"] <= 1.0
    assert snap["stages"]["splade_stage1"]["dispatches"] == 8


def test_health_reports_stage_queues_and_ewma(stack, small_corpus):
    _, _, retr = stack
    srv = RetrievalServer(ServeEngine(retr, pipeline_depth=2),
                          n_threads=1, max_batch=4, batch_timeout_ms=10)
    srv.start()
    for f in [srv.submit(r) for r in _requests(small_corpus, 8,
                                               methods=("hybrid",))]:
        f.result(timeout=60)
    h = srv.health()
    srv.stop()
    assert h["pipeline"]["depth"] == 2
    q = h["pipeline"]["queues"]["hybrid"]
    assert set(q) == {"splade_stage1", "host_gather:residuals",
                      "fused_rerank", "fused_rerank:sync"}
    assert all(depth >= 0 for depth in q.values())
    assert h["stages"]["splade_stage1"]["ewma_ms"] is not None
    # fused tail: one declared device launch per dispatch, none in the
    # sync stage, and no fuse_topk stage anywhere on the fused path
    st = h["stages"]
    assert st["fused_rerank"]["device_dispatches"] == \
        st["fused_rerank"]["dispatches"]
    assert st["fused_rerank:sync"]["device_dispatches"] == 0
    assert "fuse_topk" not in st
    assert "overlap_fraction" in h


# ---------------------------------------------------------------------------
# AccessStats thread safety
# ---------------------------------------------------------------------------

def test_access_stats_concurrent_account_and_snapshot():
    """Concurrent gather-stage accounting must not lose updates or
    corrupt the unique-page set while readers snapshot."""
    stats = AccessStats()
    stats.reset()
    N_THREADS, N_ITERS = 4, 200
    ids = np.arange(64, dtype=np.int64)

    def writer(t):
        for i in range(N_ITERS):
            stats.account(ids + t * 10_000 + i, 16,
                          residuals=(i % 2 == 0))

    def reader():
        for _ in range(N_ITERS):
            snap = stats.snapshot()
            assert snap["tokens_read"] >= snap["residual_tokens_read"]

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(N_THREADS)] + \
              [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    snap = stats.snapshot()
    assert snap["gathers"] == N_THREADS * N_ITERS
    assert snap["tokens_read"] == N_THREADS * N_ITERS * len(ids)
    assert snap["residual_gathers"] == N_THREADS * N_ITERS // 2


def test_sync_run_balances_async_window_on_error():
    """A batch dying between its opens_async dispatch and closes_async
    sync (failed device sync, crashed shard worker) must not leave the
    shared overlap accounting stuck at 'dispatch in flight'."""
    from repro.serving.pipeline import PipelineStats

    stats = PipelineStats()

    def boom(cb):
        raise RuntimeError("dies while the async window is open")

    plan = StagePlan(method="x", stages=(
        Stage("dispatch", DEVICE, lambda cb: cb, opens_async=True),
        Stage("mid", HOST, boom),
        Stage("wait", DEVICE, lambda cb: cb, closes_async=True)))
    with pytest.raises(RuntimeError):
        plan.run(CandidateBatch(method="x", k=1), stats=stats)
    assert stats._async == 0

    # raising inside the closes_async stage itself must not
    # double-close (run_stage closes the window before calling it)
    plan2 = StagePlan(method="x", stages=(
        Stage("dispatch", DEVICE, lambda cb: cb, opens_async=True),
        Stage("wait", DEVICE, boom, closes_async=True)))
    stats2 = PipelineStats()
    stats2.async_open()          # an unrelated window stays untouched
    with pytest.raises(RuntimeError):
        plan2.run(CandidateBatch(method="x", k=1), stats=stats2)
    # the plan's own window closed exactly once (net 0); the unrelated
    # window is untouched — a double-close would have zeroed it
    assert stats2._async == 1
