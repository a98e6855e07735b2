"""Concurrent serving: queue/worker mechanics, latency accounting,
failure replacement, drain, Poisson load generation, TCP front."""

import threading
import time
import types

import numpy as np
import pytest

from repro.serving.context import BatchOutcome
from repro.serving.engine import Request, ServeEngine
from repro.serving.loadgen import (run_closed_loop, run_open_loop,
                                   run_poisson_load)
from repro.serving.pipeline import PipelineStats
from repro.serving.server import RetrievalServer, TCPRetrievalServer, tcp_query


class FakeRetriever:
    """Deterministic-latency stand-in for MultiStageRetriever."""

    def __init__(self, service_s=0.002, fail_qids=()):
        self.service_s = service_s
        self.fail_qids = set(fail_qids)
        self.calls = 0

    def search_batch_ctx(self, method, q_embs=None, term_ids=None,
                         term_weights=None, alpha=None, k=10, ctxs=None):
        self.calls += 1
        if self.service_s:
            time.sleep(self.service_s)
        if any(q is not None and int(q[0]) in self.fail_qids
               for q in q_embs):
            raise RuntimeError("injected failure")
        n = len(q_embs)
        return (np.tile(np.arange(k), (n, 1)),
                np.tile(np.linspace(1, 0, k), (n, 1)), BatchOutcome())


def make_server(n_threads=2, **kw):
    srv = RetrievalServer(ServeEngine(FakeRetriever(**kw)),
                          n_threads=n_threads)
    srv.start()
    return srv


def test_serves_concurrent_requests():
    srv = make_server(n_threads=4)
    futs = [srv.submit(Request(qid=i, method="hybrid",
                               q_emb=np.zeros(2))) for i in range(32)]
    results = [f.result(timeout=30) for f in futs]
    assert len(results) == 32
    assert all(r.latency >= r.service_time - 1e-6 for r in results)
    assert srv.health()["served"] == 32
    srv.stop()


def test_failure_is_isolated_and_counted():
    srv = make_server(n_threads=2, fail_qids={5})
    ok = [srv.submit(Request(qid=i, method="hybrid",
                             q_emb=np.full(2, i))) for i in range(8)]
    with pytest.raises(RuntimeError):
        ok[5].result(timeout=10)
    for i, f in enumerate(ok):
        if i != 5:
            f.result(timeout=10)
    h = srv.health()
    assert h["failed"] == 1
    assert h["workers"] == 2     # workers survive failures
    srv.stop()


def test_cancel_while_processing_cannot_race_worker():
    """Workers claim futures (set_running_or_notify_cancel) before
    scoring, so a client cancel() mid-service fails instead of racing
    the worker's set_result into an InvalidStateError."""
    srv = make_server(n_threads=1, service_s=0.05)
    fut = srv.submit(Request(qid=0, method="hybrid", q_emb=np.zeros(2)))
    deadline = time.time() + 5
    while not fut.running() and time.time() < deadline:
        time.sleep(0.001)
    assert fut.running()
    assert not fut.cancel()              # claimed: cancel must lose
    assert fut.result(timeout=10).qid == 0
    assert srv.health()["workers"] == 1  # worker survived
    srv.stop()


def test_drain_completes_queue():
    srv = make_server(n_threads=1, service_s=0.005)
    futs = [srv.submit(Request(qid=i, method="rerank",
                               q_emb=np.zeros(2))) for i in range(10)]
    srv.drain()
    assert all(f.done() for f in futs)
    srv.stop()


def test_poisson_load_reports_percentiles():
    srv = make_server(n_threads=1, service_s=0.002)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(40)]
    res = run_poisson_load(srv, reqs, qps=400.0, seed=0)
    assert res.p95 >= res.p50 > 0
    assert len(res.latencies) == 40
    assert res.achieved_qps > 0
    srv.stop()


def test_open_loop_reports_tail_percentiles():
    """Open-loop arrivals: offered load is honoured regardless of
    service rate, and p50 <= p95 <= p99 come out of the summary."""
    srv = make_server(n_threads=1, service_s=0.001)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(30)]
    res = run_open_loop(srv, reqs, arrival_rate=500.0, seed=3)
    s = res.summary()
    assert s["n"] == 30
    assert s["p50"] <= s["p95"] <= s["p99"]
    assert res.offered_qps == 500.0
    srv.stop()


def test_open_loop_overload_grows_tail():
    """An open-loop generator must not self-throttle: offered >> service
    rate makes the tail explode relative to a light load."""
    service = 0.004
    light_srv = make_server(n_threads=1, service_s=service)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(40)]
    light = run_open_loop(light_srv, reqs, arrival_rate=50.0, seed=5)
    light_srv.stop()
    heavy_srv = make_server(n_threads=1, service_s=service)
    heavy = run_open_loop(heavy_srv, reqs, arrival_rate=2000.0, seed=5)
    heavy_srv.stop()
    assert heavy.p99 > 3 * light.p99


def test_closed_loop_survives_failed_requests():
    """A failing request must not silently kill the client thread: the
    rest of the workload still runs and is measured."""
    srv = make_server(n_threads=1, service_s=0.0, fail_qids={3})
    reqs = [Request(qid=i, method="hybrid", q_emb=np.full(2, i))
            for i in range(10)]
    res = run_closed_loop(srv, reqs, concurrency=1)
    assert len(res.latencies) == 9       # only the poisoned one missing
    srv.stop()


def test_closed_loop_self_limits():
    """Closed-loop clients never queue more than ``concurrency`` deep,
    so latency stays ~service time even though the server is slow."""
    service = 0.003
    srv = make_server(n_threads=2, service_s=service)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(24)]
    res = run_closed_loop(srv, reqs, concurrency=2)
    assert len(res.latencies) == 24
    assert res.p95 < 10 * service       # no unbounded queueing
    assert res.achieved_qps > 0
    srv.stop()


def test_poisson_offered_rate_does_not_sag():
    """Absolute-schedule arrivals: offered ≈ achieved for a fast no-op
    engine. The old relative ``sleep(gap)`` accumulated scheduler lag
    and submit overhead per arrival (coordinated omission), so at
    sub-millisecond gaps the offered rate silently sagged well below
    the requested QPS."""
    srv = make_server(n_threads=4, service_s=0.0)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(300)]
    qps = 2000.0
    res = run_poisson_load(srv, reqs, qps=qps, seed=2)
    srv.stop()
    # ideal wall ≈ last scheduled arrival; generous floor because the
    # submitting thread shares 2 cores with the servers
    assert res.achieved_qps >= 0.7 * qps, res.summary()


def test_batch_cap_resize_races_collection():
    """`_collect_batch` reads the adaptive cap under the same lock
    `_observe_latency` resizes it under; a mutator thread hammering the
    cap while batches are collected must never corrupt it (cap stays in
    [1, max_batch]) or lose requests."""
    srv = RetrievalServer(ServeEngine(FakeRetriever(service_s=0.001)),
                          n_threads=2, max_batch=8, batch_timeout_ms=1.0,
                          latency_slo_ms=5.0)
    srv.start()
    stop = threading.Event()

    def mutate():
        flip = True
        while not stop.is_set():
            with srv._lock:
                srv.batch_cap = 1 if flip else srv.max_batch
            flip = not flip

    t = threading.Thread(target=mutate, daemon=True)
    t.start()
    try:
        futs = [srv.submit(Request(qid=i, method="hybrid",
                                   q_emb=np.zeros(2)))
                for i in range(64)]
        results = [f.result(timeout=30) for f in futs]
        assert len(results) == 64
        assert 1 <= srv.batch_cap <= srv.max_batch
    finally:
        stop.set()
        t.join(timeout=5)
        srv.stop()


class _FlippableEngine:
    """Engine whose ``pipelined`` flag can change at runtime (e.g. a
    stage-1 backend switch rebuilding the pipeline)."""

    def __init__(self):
        self.pipelined = False
        self.served = 0
        self.sync_calls = 0
        self.async_calls = 0

    def _result(self, req):
        from repro.serving.engine import Result
        now = time.perf_counter()
        return Result(qid=req.qid, pids=np.arange(req.k),
                      scores=np.linspace(1, 0, req.k),
                      t_arrival=req.t_arrival, t_start=now, t_done=now)

    def process(self, req):
        self.sync_calls += 1
        self.served += 1
        return self._result(req)

    def process_batch(self, reqs):
        self.sync_calls += len(reqs)
        self.served += len(reqs)
        return [self._result(r) for r in reqs]

    def process_batch_async(self, reqs):
        from concurrent.futures import Future
        self.async_calls += len(reqs)
        self.served += len(reqs)
        fut = Future()
        fut.set_running_or_notify_cancel()
        fut.set_result([self._result(r) for r in reqs])
        return fut

    def stop_pipelines(self):
        pass

    def drain_pipelines(self, timeout=None):
        pass


def test_worker_reevaluates_pipelined_flag_mid_serve():
    """The dispatch path must follow the engine's *current* ``pipelined``
    flag, not the one captured when the worker thread started."""
    eng = _FlippableEngine()
    srv = RetrievalServer(eng, n_threads=1, max_batch=4,
                          batch_timeout_ms=1.0)
    srv.start()
    try:
        for i in range(6):
            srv.submit(Request(qid=i, method="hybrid",
                               q_emb=np.zeros(2), k=5)).result(timeout=10)
        assert eng.sync_calls == 6 and eng.async_calls == 0
        eng.pipelined = True          # rebuild happens mid-serve
        for i in range(6):
            srv.submit(Request(qid=10 + i, method="hybrid",
                               q_emb=np.zeros(2), k=5)).result(timeout=10)
        assert eng.async_calls == 6
        assert eng.sync_calls == 6    # no new sync dispatches
        eng.pipelined = False         # and back
        srv.submit(Request(qid=99, method="hybrid", q_emb=np.zeros(2),
                           k=5)).result(timeout=10)
        assert eng.sync_calls == 7
    finally:
        srv.stop()


class _RoomEngine(_FlippableEngine):
    """Synchronous stub that says whether it can start a batch: it has
    no room while ``room`` is clear. Records the qids of every batch it
    serves, and the largest batch it was asked about while full."""

    def __init__(self):
        super().__init__()
        self.room = threading.Event()
        self.room.set()
        self.batches = []
        self.held_at = 0
        self._cond = threading.Condition()
        self.retriever = types.SimpleNamespace(
            pipeline_stats=PipelineStats())

    def block(self):
        with self._cond:
            self.room.clear()
            self.held_at = 0

    def can_admit(self, reqs):
        with self._cond:
            if self.room.is_set():
                return True
            self.held_at = max(self.held_at, len(reqs))
            self._cond.notify_all()
            return False

    def wait_held(self, n, timeout=10.0):
        """Until a batch of ``n`` requests was refused for want of room."""
        with self._cond:
            return self._cond.wait_for(lambda: self.held_at >= n, timeout)

    def process(self, req):
        self.batches.append([req.qid])
        return super().process(req)

    def process_batch(self, reqs):
        self.batches.append([r.qid for r in reqs])
        return super().process_batch(reqs)


def _req(qid):
    return Request(qid=qid, method="hybrid", q_emb=np.zeros(2), k=5)


def _holds(srv):
    return srv.health()["counters"]["batch_holds"]


def test_lone_request_starts_without_waiting_out_the_batch_timeout():
    """An engine with room takes a lone request at once: the timeout
    bounds a hold while the engine is full, not every batch."""
    eng = _RoomEngine()
    srv = RetrievalServer(eng, n_threads=1, max_batch=8,
                          batch_timeout_ms=500.0)
    srv.start()
    try:
        res = srv.submit(_req(0)).result(timeout=10)
        assert res.t_start - res.t_arrival < 0.25
        assert eng.batches == [[0]]
        assert _holds(srv) == 0
    finally:
        srv.stop()


def test_requests_queued_before_start_share_a_batch_up_to_the_cap():
    eng = _RoomEngine()
    srv = RetrievalServer(eng, n_threads=1, max_batch=4,
                          batch_timeout_ms=500.0)
    futs = [srv.submit(_req(i)) for i in range(6)]
    srv.start()
    try:
        for f in futs:
            f.result(timeout=10)
        assert eng.batches == [[0, 1, 2, 3], [4, 5]]
        assert _holds(srv) == 0
    finally:
        srv.stop()


@pytest.mark.parametrize("ends", ["room", "cap", "timeout"])
def test_full_engine_holds_the_batch_open_for_arrivals(ends):
    """While the engine has no room, arrivals join the open batch. It
    goes out when room appears, when it reaches the cap, or once
    ``batch_timeout_ms`` has passed."""
    eng = _RoomEngine()
    eng.block()
    timeout_ms = 1000.0 if ends == "timeout" else 60_000.0
    srv = RetrievalServer(eng, n_threads=1,
                          max_batch=2 if ends == "cap" else 8,
                          batch_timeout_ms=timeout_ms)
    srv.start()
    try:
        first = srv.submit(_req(0))
        assert eng.wait_held(1)
        second = srv.submit(_req(1))
        if ends != "cap":
            assert eng.wait_held(2)          # joined the open batch
        if ends == "room":
            eng.room.set()
        res = [first.result(timeout=10), second.result(timeout=10)]
        assert eng.batches == [[0, 1]]
        if ends == "timeout":
            assert res[0].t_start - res[0].t_arrival >= timeout_ms / 1e3
        assert _holds(srv) == 1
    finally:
        srv.stop()


def test_batch_holds_counts_exactly_the_held_batches():
    eng = _RoomEngine()
    eng.block()
    srv = RetrievalServer(eng, n_threads=1, max_batch=2,
                          batch_timeout_ms=60_000.0)
    # full from the queue: goes out unheld, room or not
    futs = [srv.submit(_req(i)) for i in range(2)]
    srv.start()
    try:
        for f in futs:
            f.result(timeout=10)
        held = srv.submit(_req(2))           # held until room appears
        assert eng.wait_held(1)
        eng.room.set()
        held.result(timeout=10)
        srv.submit(_req(3)).result(timeout=10)   # room: not held
        eng.block()
        futs = [srv.submit(_req(4))]         # held until the cap
        assert eng.wait_held(1)
        futs.append(srv.submit(_req(5)))
        for f in futs:
            f.result(timeout=10)
        assert eng.batches == [[0, 1], [2], [3], [4, 5]]
        assert _holds(srv) == 2
    finally:
        srv.stop()


def test_workers_collecting_while_room_flips_lose_no_request():
    """Eight workers form batches while another thread flips the
    engine's room: every request is served once, in a batch within the
    cap, and no more batches are counted held than were formed."""
    import sys
    eng = _RoomEngine()
    srv = RetrievalServer(eng, n_threads=8, max_batch=4,
                          batch_timeout_ms=1.0)
    stop = threading.Event()

    def flip():
        while not stop.is_set():
            if eng.room.is_set():
                eng.block()
            else:
                eng.room.set()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    t = threading.Thread(target=flip, daemon=True)
    try:
        t.start()
        srv.start()
        futs = [srv.submit(_req(i)) for i in range(400)]
        for f in futs:
            f.result(timeout=60)
    finally:
        stop.set()
        t.join(timeout=10)
        srv.stop()
        sys.setswitchinterval(interval)
    assert not t.is_alive()
    served = sorted(q for b in eng.batches for q in b)
    assert served == list(range(400))
    assert all(1 <= len(b) <= 4 for b in eng.batches)
    assert _holds(srv) <= len(eng.batches)


def test_saturation_raises_latency():
    """Offered load ≫ service rate ⇒ queueing dominates p95 — the knee
    the paper's Fig 1/2 shows."""
    service = 0.004   # 250 QPS capacity single-thread
    low_srv = make_server(n_threads=1, service_s=service)
    reqs = [Request(qid=i, method="hybrid", q_emb=np.zeros(2))
            for i in range(60)]
    low = run_poisson_load(low_srv, reqs, qps=50.0, seed=1)
    low_srv.stop()
    hi_srv = make_server(n_threads=1, service_s=service)
    hi = run_poisson_load(hi_srv, reqs, qps=2000.0, seed=1)
    hi_srv.stop()
    assert hi.p95 > 3 * low.p95


def test_tcp_front_roundtrip():
    srv = make_server(n_threads=1)
    tcp = TCPRetrievalServer(("127.0.0.1", 0), srv)
    port = tcp.server_address[1]
    t = threading.Thread(target=tcp.serve_forever, daemon=True)
    t.start()
    try:
        out = tcp_query("127.0.0.1", port,
                        {"qid": 7, "method": "hybrid",
                         "q_emb": [0.0, 0.0], "k": 5})
        assert out["qid"] == 7
        assert len(out["pids"]) == 5
        assert out["latency"] > 0
    finally:
        tcp.shutdown()
        srv.stop()


def test_tcp_error_response_carries_qid():
    """A failing request still tells the client which qid failed."""
    srv = make_server(n_threads=1, fail_qids={9})
    tcp = TCPRetrievalServer(("127.0.0.1", 0), srv)
    port = tcp.server_address[1]
    t = threading.Thread(target=tcp.serve_forever, daemon=True)
    t.start()
    try:
        out = tcp_query("127.0.0.1", port,
                        {"qid": 9, "method": "hybrid",
                         "q_emb": [9.0, 9.0], "k": 5})
        assert "error" in out
        assert out["qid"] == 9
    finally:
        tcp.shutdown()
        srv.stop()


def test_tcp_front_answers_a_connection_burst():
    """Clients connecting all at once are queued by the listen backlog,
    not reset."""
    from concurrent.futures import ThreadPoolExecutor
    srv = make_server(n_threads=2, service_s=0.0)
    tcp = TCPRetrievalServer(("127.0.0.1", 0), srv)
    port = tcp.server_address[1]
    t = threading.Thread(target=tcp.serve_forever, daemon=True)
    t.start()
    try:
        with ThreadPoolExecutor(64) as pool:
            outs = list(pool.map(lambda i: tcp_query(
                "127.0.0.1", port, {"qid": i, "q_emb": [0.0], "k": 3}),
                range(64)))
        assert sorted(o["qid"] for o in outs) == list(range(64))
    finally:
        tcp.shutdown()
        tcp.server_close()
        srv.stop()


@pytest.mark.parametrize("env_dir", [None, "from-env"])
def test_compile_cache_placement(env_dir, tmp_path, monkeypatch):
    """The entry points' cache follows JAX_COMPILATION_CACHE_DIR when it
    is set (and then sets nothing), else a fixed <checkout>/.jax_cache."""
    import pathlib

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        got = enable_compile_cache()
        if env_dir is None:
            checkout = pathlib.Path(__file__).resolve().parents[1]
            assert got == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == got
        else:
            assert got == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
