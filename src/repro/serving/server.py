"""Concurrent serving: bounded queue + worker pool with cross-query
micro-batching, plus a TCP front.

Mirrors the paper's server-client architecture: clients submit queries
that are queued and served by ``n_threads`` workers (the paper tunes
this and lands on 1 under load — we keep it a knob and reproduce that
finding in benchmarks/bench_latency.py). Latency is measured from
arrival (enqueue) to completion, so queueing delay is included.

Micro-batching: with ``max_batch > 1`` a worker that pops a request
also takes every request already queued behind it (up to the batch
cap) and serves the group through ``ServeEngine.process_batch`` — one
batched device dispatch per stage and deduplicated mmap gathers across
co-batched queries. The batch goes out at once when the engine can
start it; only while the engine is full (a pipelined engine with
``pipeline_depth`` batches in flight) does it stay open for arrivals,
for at most ``batch_timeout_ms``, and each such hold counts as
``batch_holds``. ``max_batch=1`` preserves strict request-at-a-time
behaviour. With ``latency_slo_ms`` set, the effective batch cap adapts:
an EWMA of batch service time shrinks it under SLO pressure and grows
it back when there is headroom.

Pipelining: when the engine has ``pipeline_depth >= 2`` the worker no
longer owns a batch end-to-end — it feeds the stage-graph pipeline
(`repro.serving.pipeline`) and moves straight on to collecting the next
micro-batch while the executor resolves futures at the tail, so batch
N+1's host mmap gather overlaps batch N's device scoring.

Fault tolerance: ``drain()`` completes in-flight work; a failing batch
is retried request-by-request so one poisoned query cannot fail its
co-batched neighbours; ``stop()`` fails still-queued futures instead of
leaving clients waiting forever; ``health()`` reports queue depth,
served counts, per-stage EWMA service times / queue depths, and the
measured overlap fraction for external monitors.
"""

from __future__ import annotations

import json
import queue
import signal
import socket
import socketserver
import threading
import time
from concurrent.futures import Future
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.serving.admission import AdmissionController, RequestShed
from repro.serving.context import ADMIT_DEGRADED, ADMIT_SHED
from repro.serving.engine import Request, Result, ServeEngine
from repro.serving.pipeline import PipelineStopped, span_ids

# how often a batch held open while the engine is full looks again for
# room between arrivals
ROOM_POLL_S = 2e-4


class RetrievalServer:
    def __init__(self, engine: ServeEngine, n_threads: int = 1,
                 max_queue: int = 4096, max_batch: int = 1,
                 batch_timeout_ms: float = 2.0,
                 latency_slo_ms: Optional[float] = None,
                 slo_ewma_alpha: float = 0.25, grow_patience: int = 3,
                 admission: Optional[AdmissionController] = None):
        """``latency_slo_ms`` switches on adaptive micro-batch sizing:
        the effective batch cap shrinks (halves, floor 1) when the EWMA
        of batch service time exceeds the SLO and grows back
        (doubles, ceiling ``max_batch``) after ``grow_patience``
        consecutive under-threshold (< ~70% SLO) observations from
        batches that *fill* the current cap — growth needs evidence at
        the current operating point, not cheap small-batch samples, or
        the cap hunts between sizes and periodically blows the SLO.
        ``max_batch`` stays the hard ceiling; ``None`` keeps the cap
        fixed (PR-1 behaviour).

        ``admission``: optional :class:`AdmissionController`. Each
        ``submit`` is classified against the live per-stage EWMAs: full
        quality, degraded to the splade-only plan, or shed outright
        (the future fails with :class:`RequestShed` before the request
        ever enters the queue)."""
        self.engine = engine
        self.admission = admission
        self.sheds = 0
        self.n_threads = n_threads
        self.max_batch = max(1, max_batch)
        self.batch_timeout_ms = batch_timeout_ms
        self.latency_slo_ms = latency_slo_ms
        self.slo_ewma_alpha = slo_ewma_alpha
        self.grow_patience = max(1, grow_patience)
        self.ewma_latency_ms: Optional[float] = None
        self.batch_cap = self.max_batch      # effective (adaptive) cap
        self._grow_streak = 0
        self.queue: queue.Queue = queue.Queue(maxsize=max_queue)
        self.workers: list[threading.Thread] = []
        self.running = False
        self.failed = 0
        self._lock = threading.Lock()
        self._retry_cond = threading.Condition()
        self._retries = 0                # pipelined failure retries live
        self.tcp: Optional["TCPRetrievalServer"] = None
        self.tcp_port: Optional[int] = None
        self._shutdown_once = threading.Lock()
        self._shut_down = False

    # -- lifecycle -------------------------------------------------------
    def start(self):
        self.running = True
        for i in range(self.n_threads):
            t = threading.Thread(target=self._worker, name=f"worker-{i}",
                                 daemon=True)
            t.start()
            self.workers.append(t)

    def _collect_batch(self, first):
        """Coalesce the requests already queued behind ``first``, up to
        the current (possibly adapted) batch cap. While the engine
        cannot start the batch, keep it open for arrivals until the
        engine has room, the cap is reached or ``batch_timeout_ms``
        has passed; the ``tcp:collect`` span carries that wait as
        ``held_ms``."""
        with TraceAnnotation("tcp:collect") as span:
            batch = [first]
            # one locked read: _observe_latency resizes batch_cap under
            # self._lock from whichever thread served the last batch,
            # and a torn/stale read here could collect against a cap
            # that no longer exists
            with self._lock:
                cap = self.batch_cap
            while len(batch) < cap:
                try:
                    batch.append(self.queue.get_nowait())
                except queue.Empty:
                    break
            held_ms = 0.0
            if len(batch) < cap and not self._engine_can_admit(batch):
                stats = self._pipeline_stats()
                if stats is not None:
                    stats.counter("batch_holds")
                t0 = time.perf_counter()
                deadline = t0 + self.batch_timeout_ms / 1e3
                while (len(batch) < cap
                       and not self._engine_can_admit(batch)):
                    remaining = deadline - time.perf_counter()
                    if remaining <= 0:
                        break
                    try:
                        batch.append(self.queue.get(
                            timeout=min(remaining, ROOM_POLL_S)))
                    except queue.Empty:
                        pass
                held_ms = (time.perf_counter() - t0) * 1e3
            span.set_metadata(n=len(batch), held_ms=held_ms,
                              **span_ids([req.qid for req, _ in batch]))
        return batch

    def _engine_can_admit(self, batch) -> bool:
        """The engine's own admission check; an engine without one
        counts as able to start the batch."""
        can_admit = getattr(self.engine, "can_admit", None)
        return can_admit is None or can_admit([req for req, _ in batch])

    def _pipeline_stats(self):
        return getattr(getattr(self.engine, "retriever", None),
                       "pipeline_stats", None)

    def _observe_latency(self, results):
        """Adaptive micro-batch sizing: feed the served group's service
        time into an EWMA and resize the effective cap against
        ``latency_slo_ms`` (shrink fast, grow cautiously).

        Service time — not client-observed latency — on purpose: queueing
        delay rises exactly when the server is saturated, i.e. when
        *larger* batches are needed; feeding it back into the shrink
        decision would pin the cap at 1 under overload (positive
        feedback). Service time measures what batching actually costs a
        co-batched request."""
        if self.latency_slo_ms is None or not results:
            return
        obs_ms = max(r.service_time for r in results) * 1e3
        with self._lock:
            a = self.slo_ewma_alpha
            self.ewma_latency_ms = (obs_ms if self.ewma_latency_ms is None
                                    else a * obs_ms
                                    + (1 - a) * self.ewma_latency_ms)
            if self.ewma_latency_ms > self.latency_slo_ms:
                self.batch_cap = max(1, self.batch_cap // 2)
                self._grow_streak = 0
            elif (self.ewma_latency_ms < 0.7 * self.latency_slo_ms
                  and len(results) >= self.batch_cap
                  and self.batch_cap < self.max_batch):
                self._grow_streak += 1
                if self._grow_streak >= self.grow_patience:
                    self.batch_cap = min(self.max_batch,
                                         self.batch_cap * 2)
                    self._grow_streak = 0
            else:
                # dead band, or a batch that didn't fill the cap: no
                # evidence about the current operating point
                self._grow_streak = 0

    def _worker(self):
        while self.running:
            try:
                item = self.queue.get(timeout=0.1)
            except queue.Empty:
                continue
            batch = (self._collect_batch(item) if self.max_batch > 1
                     else [item])
            # re-read per iteration, not once at thread start: an engine
            # whose pipeline is rebuilt at runtime (stage-1 backend
            # switch, depth change) must move new batches to the new
            # dispatch path, not keep the one captured at start()
            pipelined = getattr(self.engine, "pipelined", False)
            try:
                with TraceAnnotation("tcp:dispatch", **span_ids(
                        [req.qid for req, _ in batch])):
                    if pipelined:
                        # feed the stage pipeline and move on: the tail
                        # resolves the futures while this worker
                        # collects the next micro-batch (gather/score
                        # overlap)
                        self._dispatch_pipelined(batch)
                    elif len(batch) == 1:
                        self._serve_one(*batch[0])
                    else:
                        self._serve_batch(batch)
            finally:
                for _ in batch:
                    self.queue.task_done()

    def _serve_one(self, req, fut, claimed: bool = False):
        # claim the future before any work: once RUNNING, a concurrent
        # client cancel() can no longer race our set_result/set_exception
        if not claimed and not fut.set_running_or_notify_cancel():
            return                       # cancelled while queued
        try:
            res = self.engine.process(req)
        except Exception as e:  # replace-on-failure semantics
            with self._lock:
                self.failed += 1
            fut.set_exception(e)
            return
        fut.set_result(res)
        self._observe_latency([res])

    def _serve_batch(self, batch):
        claimed = [(req, fut) for req, fut in batch
                   if fut.set_running_or_notify_cancel()]
        if not claimed:
            return
        try:
            results = self.engine.process_batch([req for req, _ in claimed])
        except Exception:
            # isolate the poisoned request: retry individually so one bad
            # query cannot fail its co-batched neighbours
            for req, fut in claimed:
                self._serve_one(req, fut, claimed=True)
            return
        for (_, fut), res in zip(claimed, results):
            fut.set_result(res)
        self._observe_latency(results)

    def _dispatch_pipelined(self, batch):
        """Feed the claimed micro-batch to the engine's stage pipeline.
        Blocks only on backpressure (head queue full); completion is
        handled at the pipeline tail by :meth:`_resolve_pipelined`."""
        claimed = [(req, fut) for req, fut in batch
                   if fut.set_running_or_notify_cancel()]
        if not claimed:
            return
        try:
            agg = self.engine.process_batch_async(
                [req for req, _ in claimed])
        except Exception as e:
            for _, fut in claimed:
                fut.set_exception(e)
            with self._lock:
                self.failed += len(claimed)
            return
        agg.add_done_callback(
            lambda f: self._resolve_pipelined(claimed, f))

    def _resolve_pipelined(self, claimed, agg):
        """Tail of the pipeline (runs on a stage worker thread): set
        per-request futures, or — keeping the synchronous path's
        isolation semantics — retry a failed batch request-by-request so
        one poisoned query cannot fail its co-batched neighbours."""
        exc = agg.exception()
        if exc is None:
            # bind once: result() re-derives the list on every call, and
            # the latency observer must see exactly the results the
            # clients got
            with TraceAnnotation("tcp:resolve", **span_ids(
                    [req.qid for req, _ in claimed])):
                results = agg.result()
                for (_, fut), res in zip(claimed, results):
                    fut.set_result(res)
                self._observe_latency(results)
            return
        if isinstance(exc, PipelineStopped) and not self.running:
            # server shutdown: fail fast instead of re-serving inline.
            # (A PipelineStopped while the server is alive — e.g. an
            # executor rebuilt by a stage-1 backend switch — falls
            # through to the retry path below instead.)
            with self._lock:
                self.failed += len(claimed)
            for req, fut in claimed:
                fut.set_exception(RuntimeError(
                    f"server stopped mid-flight for qid={req.qid}"))
            return
        # retry on a separate thread: this callback runs on a pipeline
        # stage worker, and a batch of synchronous per-request retrievals
        # here would stall every in-flight batch behind it. Tracked by a
        # counter so drain() waits for retries, not just the pipeline.
        with self._retry_cond:
            self._retries += 1

        def retry():
            try:
                for req, fut in claimed:
                    self._serve_one(req, fut, claimed=True)
            finally:
                with self._retry_cond:
                    self._retries -= 1
                    self._retry_cond.notify_all()

        threading.Thread(target=retry, name="pipeline-retry",
                         daemon=True).start()

    def stop(self):
        self.running = False
        for t in self.workers:
            t.join(timeout=2.0)
        self.workers.clear()
        # stop the stage pipeline: in-flight micro-batches resolve or
        # fail their futures (never hang) before queued ones are failed.
        # stop_pipelines (not close): the engine is caller-owned and must
        # survive a stop()/start() restart
        if hasattr(self.engine, "stop_pipelines"):
            self.engine.stop_pipelines()
        # fail whatever never got served — clients must not hang forever
        # on futures nobody will complete
        while True:
            try:
                req, fut = self.queue.get_nowait()
            except queue.Empty:
                break
            if fut.set_running_or_notify_cancel():
                fut.set_exception(
                    RuntimeError(f"server stopped before serving "
                                 f"qid={req.qid}"))
            self.queue.task_done()

    def drain(self):
        """Complete all queued work (graceful shutdown step 1). With
        pipelining, also waits for in-flight micro-batches to clear the
        stage pipeline (queue.join() returns once they are *fed*) and
        for any failure-path retries still re-serving requests."""
        self.queue.join()
        if getattr(self.engine, "pipelined", False):
            self.engine.drain_pipelines()
        with self._retry_cond:
            self._retry_cond.wait_for(lambda: self._retries == 0)

    # -- TCP front / graceful shutdown ------------------------------------
    def serve_tcp(self, host: str = "0.0.0.0", port: int = 0
                  ) -> "TCPRetrievalServer":
        """Attach the TCP front. ``port=0`` binds an ephemeral port —
        the kernel picks a free one, so CI smokes can never clash — and
        the *real* port is reported in :attr:`tcp_port`, ``health()``,
        and on stdout. The caller runs ``.serve_forever()`` (or puts it
        on a thread)."""
        self.tcp = TCPRetrievalServer((host, port), self)
        self.tcp_port = self.tcp.server_address[1]
        print(f"RETRIEVAL_PORT={self.tcp_port}", flush=True)
        return self.tcp

    def shutdown_gracefully(self):
        """Drain, then stop — the SIGTERM path. Stops accepting new TCP
        connections first, completes everything queued (including
        in-flight pipeline batches and failure retries), then stops the
        workers. Idempotent, and the lock is held for the *whole*
        drain: a second caller (the launcher's exit path racing the
        SIGTERM handler thread) blocks until the drain completes
        instead of returning early and tearing the engine down under
        in-flight batches."""
        with self._shutdown_once:
            if self._shut_down:
                return
            if self.tcp is not None:
                self.tcp.shutdown()
            self.drain()
            self.stop()
            self._shut_down = True

    def install_sigterm_handler(self):
        """Route SIGTERM to :meth:`shutdown_gracefully` on a separate
        thread (``TCPServer.shutdown`` deadlocks if called from the
        thread running ``serve_forever``, which is where the signal
        lands). Returns the previous handler. Main thread only — signal
        registration is a CPython restriction."""
        def handler(signum, frame):
            threading.Thread(target=self.shutdown_gracefully,
                             name="sigterm-drain", daemon=True).start()

        return signal.signal(signal.SIGTERM, handler)

    # -- client API -------------------------------------------------------
    def submit(self, req: Request) -> Future:
        """Front door: exact-cache fast path → admission → queue.

        A cache hit resolves the future immediately without touching
        the queue (bitwise the cold answer, near-zero latency). The
        admission controller then classifies the request against the
        live per-stage EWMAs: a shed fails the future with
        :class:`RequestShed`; a degrade stamps the request's context so
        the engine runs the splade-only plan."""
        req.t_arrival = time.perf_counter()
        fut: Future = Future()
        engine = self.engine
        if (req.ctx is None and hasattr(engine, "context_for")
                and (self.admission is not None
                     or getattr(engine, "caches", None) is not None)):
            req.ctx = engine.context_for(req)
        hit = (engine.cache_lookup(req, count_miss=False)
               if hasattr(engine, "cache_lookup") else None)
        if hit is not None:
            fut.set_running_or_notify_cancel()
            fut.set_result(hit)
            return fut
        if self.admission is not None:
            stats = self._pipeline_stats()
            snap = stats.snapshot()["stages"] if stats is not None else {}
            degradable = (req.method in ("hybrid", "rerank")
                          and req.term_ids is not None
                          and len(req.term_ids) > 0)
            with self._lock:
                cap = self.batch_cap
            d = self.admission.decide(
                req.method, degradable, snap,
                queue_depth=self.queue.qsize(), batch_cap=cap,
                deadline_ms=req.deadline_ms)
            if d.admission == ADMIT_SHED:
                with self._lock:
                    self.sheds += 1
                fut.set_running_or_notify_cancel()
                fut.set_exception(RequestShed(d.reason,
                                              d.predicted_full_ms))
                return fut
            if d.admission == ADMIT_DEGRADED and req.ctx is not None:
                req.ctx = req.ctx.degraded(d.reason)
        self.queue.put((req, fut))
        return fut

    def health(self) -> dict:
        """Server vitals. Beyond the batch-level EWMA, reports the
        per-stage instrumentation (EWMA service time, wall, queue wait,
        mmap pages) whenever the retriever keeps one, and — under
        pipelining — per-stage queue depths and the measured
        host/device overlap fraction, so the adaptive ``latency_slo_ms``
        controller can be debugged per stage."""
        h = {"queue_depth": self.queue.qsize(),
             "served": self.engine.served,
             "failed": self.failed,
             "sheds": self.sheds,
             "workers": sum(t.is_alive() for t in self.workers),
             "batch_cap": self.batch_cap,
             "ewma_latency_ms": self.ewma_latency_ms,
             "port": self.tcp_port,
             "n_shards": getattr(getattr(self.engine, "retriever", None),
                                 "n_shards", 1)}
        retr = getattr(self.engine, "retriever", None)
        if hasattr(retr, "worker_health"):
            # process-group backend: per-shard worker vitals (pid, RSS,
            # mmap segment bytes, restarts, replica health) for
            # external monitors
            h["shard_workers"] = retr.worker_health()
        if hasattr(retr, "degraded_shards"):
            h["degraded_shards"] = retr.degraded_shards()
            h["allow_degraded"] = getattr(retr, "allow_degraded", False)
        stats = self._pipeline_stats()
        if stats is not None:
            snap = stats.snapshot()
            h["stages"] = {
                name: {"ewma_ms": r["ewma_ms"], "wall_s": r["wall_s"],
                       "dispatches": r["dispatches"],
                       "device_dispatches": r["device_dispatches"],
                       "pages_touched": r["pages_touched"],
                       "h2d_bytes": r["h2d_bytes"]}
                for name, r in snap["stages"].items()}
            h["overlap_fraction"] = snap["overlap_fraction"]
            h["counters"] = {"jax_compiles": 0, "jax_compile_ms": 0.0,
                             "batch_holds": 0, **snap.get("counters", {})}
        live = getattr(retr, "live", None)
        if live is not None:
            h["live"] = (retr.live_stats() if hasattr(retr, "live_stats")
                         else live.stats())
            h["index_generation"] = getattr(retr, "index_generation", 0)
        if self.admission is not None:
            h["admission"] = self.admission.stats()
        caches = getattr(self.engine, "caches", None)
        if caches is not None:
            h["caches"] = caches.stats()
        if getattr(self.engine, "pipelined", False):
            h["pipeline"] = self.engine.pipeline_health()
        return h


# ---------------------------------------------------------------------------
# Minimal TCP front (newline-delimited JSON) for the runnable example.
# ---------------------------------------------------------------------------

class _Handler(socketserver.StreamRequestHandler):
    def _admin(self, msg, op):
        """Control-plane ops share the query socket, dispatched on an
        explicit ``op`` key so plain query lines stay wire-compatible:
        live mutations (upsert/delete), compaction, and health/stats.
        Mutations go through the engine pass-throughs, so they require
        a live-enabled retriever (``--live``) and fail cleanly — as an
        ``error`` reply, not a dropped connection — on a frozen one."""
        rs = self.server.retrieval
        engine = rs.engine
        if op == "upsert":
            pid = engine.live_upsert(
                np.asarray(msg["doc_emb"], np.float32),
                np.asarray(msg.get("term_ids", []), np.int32),
                np.asarray(msg.get("term_weights", []), np.float32),
                msg.get("doc_len"))
            return {"ok": True, "pid": int(pid)}
        if op == "delete":
            return {"ok": bool(engine.live_delete(int(msg["pid"])))}
        if op == "compact":
            out = engine.live_compact()
            return {"ok": True,
                    "compacted": 0 if not out else int(out["compacted"])}
        if op == "live_stats":
            return {"ok": True, "live": engine.live_stats()}
        if op == "health":
            return {"ok": True, "health": rs.health()}
        raise ValueError(f"unknown op {op!r}")

    def handle(self):
        for line in self.rfile:
            with TraceAnnotation("tcp:request") as span:
                out = self._answer(line, span)
                with TraceAnnotation("tcp:json_dumps"):
                    reply = (json.dumps(out) + "\n").encode()
                with TraceAnnotation("tcp:write"):
                    self.wfile.write(reply)
                    self.wfile.flush()

    def _answer(self, line, span) -> dict:
        """The reply to one request line: an admin op's result, a
        query's answer, or an error. Names the request's ``qid`` on its
        ``tcp:request`` span once the line is parsed."""
        qid = None
        try:
            with TraceAnnotation("tcp:json_loads"):
                msg = json.loads(line)
            qid = msg.get("qid")
            if qid is not None:
                span.set_metadata(qid=qid)
            op = msg.get("op")
            if op is not None:
                return self._admin(msg, op)
            req = Request(
                qid=msg["qid"], method=msg.get("method", "hybrid"),
                q_emb=np.asarray(msg["q_emb"], np.float32)
                if "q_emb" in msg else None,
                term_ids=np.asarray(msg.get("term_ids", []), np.int32),
                term_weights=np.asarray(msg.get("term_weights", []),
                                        np.float32),
                k=msg.get("k", 10))
            with TraceAnnotation("tcp:await", qid=req.qid):
                res = self.server.retrieval.submit(req).result(timeout=60)
            out = {"qid": res.qid, "pids": res.pids.tolist(),
                   "scores": [float(s) for s in res.scores],
                   "latency": res.latency}
            if res.cache_hit:
                out["cache_hit"] = True
            if res.degraded:
                # partial or downgraded answer: the reason code says
                # whether shards were missing or admission control
                # ran the cheap plan
                out["degraded"] = True
                out["degrade_reason"] = res.degrade_reason
                out["missing_shards"] = list(res.missing_shards)
        except RequestShed as e:
            out = {"error": str(e), "shed": True, "reason": e.reason}
            if qid is not None:
                out["qid"] = qid
        except Exception as e:
            out = {"error": str(e)}
            if qid is not None:
                out["qid"] = qid
        return out


class TCPRetrievalServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True
    # listen backlog: socketserver's default of 5 resets connections when
    # a burst of clients connects at once
    request_queue_size = 128

    def __init__(self, addr, retrieval_server: RetrievalServer):
        super().__init__(addr, _Handler)
        self.retrieval = retrieval_server


def tcp_query(host: str, port: int, payload: dict) -> dict:
    with socket.create_connection((host, port), timeout=60) as s:
        s.sendall((json.dumps(payload) + "\n").encode())
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
    return json.loads(buf)
