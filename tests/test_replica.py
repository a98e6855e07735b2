"""Replicated shard fabric: replica-set routing units (EWMA order,
circuit breaker, quarantine budgets, hedge budgets), per-op deadlines
(``DeadlineExceeded``), seeded fault injection (``FaultSpec`` /
``FaultyChannel`` determinism + prob-0 transparency), remote-endpoint
attach parity, SIGKILL failover with zero lost batches, and the
degraded path — all replicas of a shard down → flagged partial
answers over the survivors, healing back to bitwise parity."""

import dataclasses
import os
import signal
import socket
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.sharded import build_shard_group
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.sharding import load_group, split_index_tree
from repro.index.splade_index import SpladeIndex, build_splade_index
from repro.serving.engine import Request, ServeEngine
from repro.serving.replica import ReplicaSet, _Replica
from repro.serving.server import RetrievalServer, tcp_query
from repro.serving.transport import (DeadlineExceeded, FaultSpec,
                                     FaultyChannel, ShardUnavailable,
                                     ShardWorkerDied, StreamChannel)
from repro.serving.transport.client import ShardWorkerClient

PLAID = PlaidParams(nprobe=8, candidate_cap=512, ndocs=128, k=50)
MS = MultiStageParams(first_k=50, k=20)


# ---------------------------------------------------------------------------
# error taxonomy
# ---------------------------------------------------------------------------

def test_error_taxonomy():
    """Deadlines are connection-class failures (the failover machinery
    treats both alike) and ShardUnavailable is a ShardWorkerDied so
    legacy ``except ShardWorkerDied`` handlers keep working."""
    assert issubclass(DeadlineExceeded, ConnectionError)
    e = ShardUnavailable("gone", shard=3, last_error=ValueError("x"))
    assert isinstance(e, ShardWorkerDied)
    assert e.shard == 3
    assert isinstance(e.last_error, ValueError)


# ---------------------------------------------------------------------------
# _Replica / ReplicaSet units (no processes)
# ---------------------------------------------------------------------------

class _FakeCli:
    def __init__(self, alive=True, spawn_fail=False):
        self._alive = alive
        self.spawn_fail = spawn_fail
        self.terminated = False

    pid = 1234

    def alive(self):
        return self._alive

    def spawn(self):
        if self.spawn_fail:
            raise RuntimeError("spawn boom")
        self._alive = True
        return {}

    def terminate(self, grace_s=5.0):
        self.terminated = True
        return -9


def _mk_replica(endpoint=None, **cli_kw):
    return _Replica(0, 0, lambda gen: _FakeCli(**cli_kw),
                    endpoint=endpoint)


def test_replica_fail_fast_reaps_then_raises_then_respawns():
    r = _mk_replica()
    cli = r.ensure(fail_fast=True)
    cli._alive = False                        # the worker died
    with pytest.raises(ShardWorkerDied, match="healing on next use"):
        r.ensure(fail_fast=True)
    assert cli.terminated and r.restarts == 1 and r.serve_failures == 1
    assert r.ensure(fail_fast=True).alive()   # next use respawns


def test_replica_local_serve_quarantine_budget():
    r = _mk_replica()
    for _ in range(2):                        # die, respawn, die again
        r.ensure(fail_fast=False)._alive = False
        try:
            r.ensure(fail_fast=False)
        except ShardWorkerDied:
            pass
    assert r.quarantined()
    with pytest.raises(ShardWorkerDied, match="not respawning"):
        r.ensure(fail_fast=False)


def test_replica_local_spawn_quarantine_budget_is_separate():
    r = _mk_replica(spawn_fail=True)
    for _ in range(2):
        with pytest.raises(RuntimeError):
            r.ensure(fail_fast=False)
    assert r.spawn_failures == 2 and r.serve_failures == 0
    assert r.quarantined()
    with pytest.raises(ShardWorkerDied,
                       match="failed to spawn twice"):
        r.ensure(fail_fast=False)


def test_remote_replica_never_quarantines():
    r = _mk_replica(endpoint="127.0.0.1:1")
    for _ in range(5):
        r.ensure(fail_fast=False)._alive = False
        r.consec_serve_failures += 0          # streak grows via ensure
        r.ensure(fail_fast=False)             # reconnect revives inline
    assert not r.quarantined()
    # a successful reconnect wiped the streak every time
    assert r.consec_serve_failures == 0


def test_route_order_prefers_fast_live_closed_breakers():
    reps = [_mk_replica() for _ in range(4)]
    rs = ReplicaSet(0, reps)
    for r in reps[:3]:
        r.ensure(fail_fast=False)
    reps[0].ewma_ms, reps[1].ewma_ms = 30.0, 5.0
    reps[2].breaker_open_until = time.monotonic() + 10.0   # cooling
    # reps[3] never spawned: dead-but-spawnable, no EWMA
    order = rs.route_order()
    assert order[0] is reps[1]                # fastest live first
    assert order[1] is reps[0]
    assert order[2] is reps[3]                # spawnable before cooling
    assert order[3] is reps[2]                # half-open probe last
    assert reps[1] not in rs.route_order(exclude=reps[1])


def test_breaker_cooldown_grows_and_success_resets():
    rs = ReplicaSet(0, [_mk_replica()], breaker_base_ms=100.0,
                    breaker_max_ms=400.0)
    r = rs.primary
    cools = []
    for _ in range(4):
        rs.record_failure(r)
        cools.append(r.breaker_open_until - time.monotonic())
    assert cools[1] > cools[0] and cools[2] > cools[1]
    assert cools[3] <= 0.401 + 0.05           # capped at breaker_max
    rs.record_success(r, elapsed_ms=12.0)
    assert r.breaker_level == 0 and r.breaker_open_until == 0.0
    assert r.ewma_ms == 12.0
    rs.record_success(r, elapsed_ms=24.0)     # EWMA alpha = 0.2
    assert abs(r.ewma_ms - (0.8 * 12.0 + 0.2 * 24.0)) < 1e-9


def test_acquire_exhaustion_raises_shard_unavailable():
    reps = [_mk_replica(spawn_fail=True) for _ in range(2)]
    rs = ReplicaSet(4, reps)
    with pytest.raises(RuntimeError):
        # spawn failures propagate their own error the first time; two
        # of them quarantine each local replica
        rs.acquire()
    for r in reps:
        r.consec_spawn_failures = 2
    with pytest.raises(ShardUnavailable) as ei:
        rs.acquire()
    assert ei.value.shard == 4
    assert "all 2 replica(s) unavailable" in str(ei.value)


def test_hedge_budget_gating():
    reps = [_mk_replica(), _mk_replica()]
    rs = ReplicaSet(0, reps, hedge_factor=2.0, hedge_floor_ms=50.0)
    r = reps[0]
    assert rs.hedge_budget_ms(r) is None      # no EWMA yet
    r.ewma_ms = 100.0
    assert rs.hedge_budget_ms(r) is None      # no live sibling
    reps[1].ensure(fail_fast=False)
    assert rs.hedge_budget_ms(r) == 200.0     # factor * ewma
    r.ewma_ms = 10.0
    assert rs.hedge_budget_ms(r) == 50.0      # floor wins
    assert ReplicaSet(0, reps).hedge_budget_ms(r) is None  # hedging off
    assert ReplicaSet(0, [reps[0]], hedge_factor=2.0) \
        .hedge_budget_ms(r) is None           # no siblings at all


# ---------------------------------------------------------------------------
# FaultSpec / FaultyChannel
# ---------------------------------------------------------------------------

class _RecChannel:
    sock = None
    transport = "fake"

    def __init__(self):
        self.sent = []

    def send(self, obj):
        self.sent.append(obj)
        return 7

    def stats(self):
        return {"transport": "fake", "bytes_sent": 0, "bytes_recv": 0,
                "bytes_copied": 0, "bytes_zero_copy": 0}

    def close(self):
        pass


def test_fault_spec_parse():
    s = FaultSpec.parse("seed=42,drop=0.05,delay=20:0.1,"
                        "truncate=0.02,corrupt=0.03")
    assert (s.seed, s.drop, s.delay_ms, s.delay_p, s.truncate,
            s.corrupt) == (42, 0.05, 20.0, 0.1, 0.02, 0.03)
    assert FaultSpec.parse("delay=5").delay_p == 1.0   # bare delay
    with pytest.raises(ValueError, match="unknown fault field"):
        FaultSpec.parse("jitter=0.5")


def test_prob_zero_faulty_channel_is_transparent():
    inner = _RecChannel()
    ch = FaultyChannel(inner, FaultSpec())
    for i in range(20):
        assert ch.send({"i": i}) == 7
    assert len(inner.sent) == 20
    assert all(v == 0 for v in ch.faults.values())
    assert ch.stats()["faults_injected"] == ch.faults
    assert ch.transport == "fake"             # delegation intact


def test_faulty_channel_schedule_is_seed_deterministic():
    def run():
        inner = _RecChannel()
        ch = FaultyChannel(inner, FaultSpec(seed=9, drop=0.3,
                                            delay_ms=1.0, delay_p=0.2))
        delivered = []
        for i in range(60):
            ch.send({"i": i})
            delivered.append(len(inner.sent))
        return delivered, dict(ch.faults)

    a, b = run(), run()
    assert a == b                             # pure fn of (seed, index)
    assert a[1]["drop"] > 0                   # and it actually fired


# ---------------------------------------------------------------------------
# per-op deadlines against a stalling worker
# ---------------------------------------------------------------------------

@pytest.fixture
def stall_worker():
    """A fake remote worker that answers the readiness ping and then
    never replies again — a hung process as seen from the wire."""
    srv = socket.create_server(("127.0.0.1", 0))
    stop = threading.Event()

    def run():
        srv.settimeout(0.2)
        while not stop.is_set():
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            ch = StreamChannel(conn)
            try:
                while not stop.is_set():
                    msg = ch.recv(timeout=0.5)
                    if msg is None:
                        continue
                    if msg["op"] == "ping":
                        ch.send({"ok": True, "result": {"pid": 0}})
                    # any other op: stall forever
            except Exception:
                pass
            finally:
                ch.close()

    t = threading.Thread(target=run, daemon=True)
    t.start()
    yield srv.getsockname()[1]
    stop.set()
    t.join(timeout=5)
    srv.close()


def test_per_op_deadline_raises_and_marks_dead(stall_worker):
    cli = ShardWorkerClient(0, "unused", endpoint=f"127.0.0.1:"
                            f"{stall_worker}")
    cli.spawn()
    assert cli.alive()
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceeded, match="per-op deadline"):
        cli.call("splade", {}, timeout_ms=200.0)
    assert time.monotonic() - t0 < 5.0        # the deadline, not the
    assert not cli.alive()                    # 300s call timeout
    cli.terminate(grace_s=0.1)


def test_no_deadline_uses_soft_timeout_path(stall_worker):
    cli = ShardWorkerClient(0, "unused", endpoint=f"127.0.0.1:"
                            f"{stall_worker}")
    cli.spawn()
    from repro.serving.rpc import ShardWorkerError
    with pytest.raises(ShardWorkerError, match="soft RPC deadline"):
        cli.call("splade", {}, timeout=0.3, kill_on_timeout=False)
    assert cli.alive()                        # soft expiry never kills
    cli.terminate(grace_s=0.1)


# ---------------------------------------------------------------------------
# fixtures: shard split + a fleet of standalone remote workers
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def base_dir(tmp_path_factory, small_corpus):
    base = tmp_path_factory.mktemp("replica_base")
    build_colbert_index(base / "colbert", small_corpus["doc_embs"],
                        small_corpus["doc_lens"], nbits=4,
                        n_centroids=128, kmeans_iters=4)
    build_splade_index(small_corpus["doc_term_ids"],
                       small_corpus["doc_term_weights"],
                       small_corpus["cfg"].vocab,
                       small_corpus["cfg"].n_docs).save(base / "splade")
    return base


@pytest.fixture(scope="module")
def split(base_dir):
    group = split_index_tree(base_dir, 2)
    return load_group(group)


@pytest.fixture(scope="module")
def thread_ref(split):
    dirs, bounds = split
    return build_shard_group(dirs, bounds, workers="thread",
                             mode="mmap", plaid_params=PLAID,
                             multistage_params=MS)


@pytest.fixture(scope="module")
def remote_fleet(split):
    """2 shards x 2 replicas of standalone TCP workers, each its own
    independently killable process. Tests that kill workers restore
    them (same ports) before yielding back."""
    from repro.serving.worker import spawn_standalone

    dirs, bounds = split

    def spawn(shard, port=0):
        return spawn_standalone(
            dirs[shard], shard, port=port,
            plaid_params=dataclasses.asdict(PLAID),
            ms_params=dataclasses.asdict(MS))

    slots = [(i, r) for i in range(2) for r in range(2)]
    with ThreadPoolExecutor(4) as tp:
        procs = list(tp.map(lambda s: spawn(s[0]), slots))
    fleet = {"dirs": dirs, "bounds": bounds, "spawn": spawn,
             "workers": {s: {"proc": p, "port": port}
                         for s, (p, port) in zip(slots, procs)}}
    yield fleet
    for w in fleet["workers"].values():
        w["proc"].kill()
    for w in fleet["workers"].values():
        try:
            w["proc"].wait(timeout=10)
        except Exception:
            pass


def _endpoints(fleet):
    return [[f"127.0.0.1:{fleet['workers'][(i, r)]['port']}"
             for r in range(2)] for i in range(2)]


def _coordinator(fleet, **kw):
    return build_shard_group(
        fleet["dirs"], fleet["bounds"], workers="process", mode="mmap",
        plaid_params=PLAID, multistage_params=MS, replicas=0,
        replica_endpoints=_endpoints(fleet), **kw)


def _kill(fleet, shard, rid):
    w = fleet["workers"][(shard, rid)]
    w["proc"].kill()
    w["proc"].wait(timeout=10)


def _restore(fleet):
    for (i, r), w in fleet["workers"].items():
        if w["proc"].poll() is not None:
            w["proc"], w["port"] = fleet["spawn"](i, w["port"])


def _batch(corpus, lo, hi):
    return dict(q_embs=corpus["q_embs"][lo:hi],
                term_ids=corpus["q_term_ids"][lo:hi],
                term_weights=corpus["q_term_weights"][lo:hi])


# ---------------------------------------------------------------------------
# remote endpoints: attach parity, SIGKILL failover, degraded + heal
# ---------------------------------------------------------------------------

def test_remote_attach_parity(remote_fleet, thread_ref, small_corpus):
    """A coordinator over TCP endpoints returns bitwise the thread
    group's results — remote transport changes nothing."""
    g = _coordinator(remote_fleet)
    try:
        kw = _batch(small_corpus, 0, 6)
        for method in ("splade", "hybrid"):
            ref = thread_ref.search_batch(method, **kw, k=10)
            got = g.search_batch(method, **kw, k=10)
            np.testing.assert_array_equal(ref[0], got[0])
            np.testing.assert_array_equal(ref[1], got[1])
        h = g.worker_health()
        assert all(rec["alive_replicas"] == 2 for rec in h)
        assert all(len(rec["replicas"]) == 2 for rec in h)
        assert all("spawn_failures" in rec and "serve_failures" in rec
                   for rec in h)
        assert all(r["endpoint"] for rec in h for r in rec["replicas"])
    finally:
        g.close()
        _restore(remote_fleet)


def test_remote_sigkill_failover_keeps_serving(remote_fleet, thread_ref,
                                               small_corpus):
    """SIGKILL one replica of every shard between batches: the next
    batches must succeed bitwise via the sibling replicas — zero
    failed requests, failover counted."""
    g = _coordinator(remote_fleet, op_deadline_ms=10_000.0)
    try:
        kw = _batch(small_corpus, 0, 4)
        ref = thread_ref.search_batch("hybrid", **kw, k=10)
        got = g.search_batch("hybrid", **kw, k=10)
        np.testing.assert_array_equal(ref[0], got[0])
        for shard in range(2):
            _kill(remote_fleet, shard, 0)
            # a killed remote worker is invisible until an op lands on
            # it (liveness is the connection); zero the corpse's EWMA
            # so routing deterministically picks it first and the
            # failover path — not lucky sibling routing — is what
            # keeps the batch alive
            g._replica_sets[shard].replicas[0].ewma_ms = 0.0
        for _ in range(3):                    # several batches post-kill
            got = g.search_batch("hybrid", **kw, k=10)
            np.testing.assert_array_equal(ref[0], got[0])
            np.testing.assert_array_equal(ref[1], got[1])
        counters = g.pipeline_stats.snapshot()["counters"]
        assert counters.get("failover_retries", 0) >= 1
        assert g.degraded_shards() == []      # siblings kept both up
        # restart the killed workers at their old ports; routing (or
        # the healer) reconnects and the full replica set comes back
        _restore(remote_fleet)
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            g.search_batch("hybrid", **kw, k=10)
            if all(rec["alive_replicas"] == 2
                   for rec in g.worker_health()):
                break
            time.sleep(0.5)
        assert all(rec["alive_replicas"] == 2
                   for rec in g.worker_health())
        got = g.search_batch("hybrid", **kw, k=10)
        np.testing.assert_array_equal(ref[0], got[0])
        np.testing.assert_array_equal(ref[1], got[1])
    finally:
        g.close()
        _restore(remote_fleet)


def test_all_replicas_down_degrades_then_heals(remote_fleet, split,
                                               small_corpus):
    """Both replicas of shard 1 SIGKILLed: an ``allow_degraded``
    coordinator answers from shard 0 alone — flagged, with the missing
    shard named, surviving results exact — and returns to bitwise
    parity once the workers come back."""
    dirs, bounds = split
    g = _coordinator(remote_fleet, allow_degraded=True,
                     op_deadline_ms=10_000.0)
    try:
        kw = _batch(small_corpus, 0, 4)
        ref = g.search_batch("splade", **kw, k=10)
        assert g.last_missing_shards() == ()
        _kill(remote_fleet, 1, 0)
        _kill(remote_fleet, 1, 1)
        out = g.search_batch("splade", **kw, k=10)
        missing = g.last_missing_shards()
        assert missing == (1,)
        assert g.degraded_shards() == [1]
        assert (out[0][out[0] >= 0] < bounds[1]).all()
        # surviving-shard exactness: the degraded answer IS shard 0's
        # own top-k (shard 0 starts at pid 0, so local pids == global)
        shard0 = MultiStageRetriever(
            SpladeIndex.load(dirs[0] / "splade", mmap=True),
            PLAIDSearcher(ColBERTIndex(dirs[0] / "colbert",
                                       mode="mmap"), PLAID), MS)
        ref0 = shard0.search_batch("splade", **kw, k=10)
        np.testing.assert_array_equal(ref0[0], out[0])
        np.testing.assert_array_equal(ref0[1], out[1])
        # one degraded call counts once: search_batch adds no count of
        # its own to search_batch_ctx's
        assert g.pipeline_stats.snapshot()["counters"][
            "degraded_batches"] == 1
        # recovery: restart both workers → full bitwise parity again
        _restore(remote_fleet)
        deadline = time.monotonic() + 60
        healed = out
        while time.monotonic() < deadline:
            healed = g.search_batch("splade", **kw, k=10)
            if g.last_missing_shards() == ():
                break
            time.sleep(0.5)
        assert g.degraded_shards() == []
        np.testing.assert_array_equal(ref[0], healed[0])
        np.testing.assert_array_equal(ref[1], healed[1])
    finally:
        g.close()
        _restore(remote_fleet)


# ---------------------------------------------------------------------------
# local replicas: sibling routing + the healer thread
# ---------------------------------------------------------------------------

def test_local_replicas_route_around_dead_primary(split, thread_ref,
                                                  small_corpus):
    """2 local replicas per shard: SIGKILL the primary child — traffic
    routes to the live sibling with no failed batch, and the healer
    respawns the corpse in the background."""
    dirs, bounds = split
    g = build_shard_group(dirs, bounds, workers="process", mode="mmap",
                          plaid_params=PLAID, multistage_params=MS,
                          replicas=2)
    try:
        kw = _batch(small_corpus, 0, 4)
        ref = thread_ref.search_batch("splade", **kw, k=10)
        got = g.search_batch("splade", **kw, k=10)
        np.testing.assert_array_equal(ref[0], got[0])
        victim = g._replica_sets[0].primary.client
        os.kill(victim.pid, signal.SIGKILL)
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if victim.proc.poll() is not None:
                break
            time.sleep(0.05)
        got = g.search_batch("splade", **kw, k=10)   # sibling serves
        np.testing.assert_array_equal(ref[0], got[0])
        np.testing.assert_array_equal(ref[1], got[1])
        deadline = time.monotonic() + 60             # healer respawns
        while time.monotonic() < deadline:
            if g._replica_sets[0].alive_count() == 2:
                break
            time.sleep(0.25)
        assert g._replica_sets[0].alive_count() == 2
        assert g.pipeline_stats.snapshot()["counters"].get(
            "replica_heals", 0) >= 1
        rec = g.worker_health()[0]
        assert rec["restarts"] >= 1 and rec["serve_failures"] >= 1
        got = g.search_batch("splade", **kw, k=10)
        np.testing.assert_array_equal(ref[0], got[0])
    finally:
        g.close()


# ---------------------------------------------------------------------------
# degraded results through engine + server (local single-replica group)
# ---------------------------------------------------------------------------

def test_degraded_flags_through_engine_server_and_tcp(split, thread_ref,
                                                      small_corpus):
    """Single-replica group with allow_degraded: kill shard 1 → the
    next request is a flagged partial answer (engine Result fields,
    server health, TCP response); the request after that heals back to
    the full answer."""
    dirs, bounds = split
    g = build_shard_group(dirs, bounds, workers="process", mode="mmap",
                          plaid_params=PLAID, multistage_params=MS,
                          allow_degraded=True)
    engine = ServeEngine(g, own_retriever=True)
    srv = RetrievalServer(engine, n_threads=1)
    srv.start()
    tcp = srv.serve_tcp("127.0.0.1", 0)
    tcp_thread = threading.Thread(target=tcp.serve_forever, daemon=True)
    tcp_thread.start()
    try:
        def req(qid):
            return Request(qid=qid, method="splade",
                           term_ids=small_corpus["q_term_ids"][qid],
                           term_weights=small_corpus[
                               "q_term_weights"][qid], k=10)

        def kill_shard1():
            victim = g._clients[1]
            os.kill(victim.pid, signal.SIGKILL)
            deadline = time.monotonic() + 10
            while time.monotonic() < deadline:
                if victim.proc.poll() is not None:
                    return
                time.sleep(0.05)
            raise AssertionError("worker refused to die")

        full = srv.submit(req(0)).result(timeout=120)
        assert not full.degraded and full.missing_shards == ()

        kill_shard1()
        part = srv.submit(req(0)).result(timeout=120)
        assert part.degraded and part.missing_shards == (1,)
        assert (part.pids[part.pids >= 0] < bounds[1]).all()
        # a request served alone (a batch of one) counts as a degraded
        # batch
        assert g.pipeline_stats.snapshot()["counters"][
            "degraded_batches"] == 1

        # the degraded request reaped the corpse and has not respawned
        # it yet (heal-on-next-use), so health names the missing shard
        h = srv.health()
        assert h["allow_degraded"] is True
        assert h["degraded_shards"] == [1]

        healed = srv.submit(req(0)).result(timeout=120)
        assert not healed.degraded
        np.testing.assert_array_equal(full.pids, healed.pids)
        np.testing.assert_array_equal(full.scores, healed.scores)

        # same choreography through the TCP front: the degraded reply
        # carries the flag + missing ids, the healed one carries neither
        kill_shard1()
        deg = tcp_query("127.0.0.1", srv.tcp_port, {
            "qid": 7, "method": "splade",
            "term_ids": small_corpus["q_term_ids"][7].tolist(),
            "term_weights":
                small_corpus["q_term_weights"][7].tolist(), "k": 10})
        assert "error" not in deg
        assert deg["degraded"] is True and deg["missing_shards"] == [1]
        assert g.pipeline_stats.snapshot()["counters"][
            "degraded_batches"] == 2
        ok = tcp_query("127.0.0.1", srv.tcp_port, {
            "qid": 8, "method": "splade",
            "term_ids": small_corpus["q_term_ids"][8].tolist(),
            "term_weights":
                small_corpus["q_term_weights"][8].tolist(), "k": 10})
        assert "degraded" not in ok and "error" not in ok
    finally:
        tcp.shutdown()
        tcp.server_close()
        srv.stop()
        engine.close()
