"""Stage-graph pipeline executor: overlap host mmap gathers with device
scoring across micro-batches.

The paper's tension is that memory-mapped scoring trades RAM for
page-fault latency. Serving a micro-batch strictly serially leaves the
device idle while the OS pages residuals in, and leaves the mmap idle
while kernels run. This module restructures the serving hot path around
*stages*:

* each retrieval method compiles to a :class:`StagePlan` — an ordered
  tuple of typed :class:`Stage` steps (``splade_stage1``,
  ``plaid_probe``, ``host_gather``, ``device_score``, ``fuse_topk``)
  that pass an immutable :class:`CandidateBatch` carrier instead of
  positional arrays threaded through ``multistage.py``;
* :class:`PipelineExecutor` runs host-bound and device-bound stages on
  separate kind-based worker threads connected by queues, with
  ``depth`` bounding the batches in flight, so micro-batch N+1's
  host-bound gather overlaps micro-batch N's device-bound dispatch
  (JAX dispatch and numpy mmap reads both release the GIL);
* :class:`PipelineStats` is the single per-stage instrumentation
  record — wall time, dispatches, queries, EWMA service time, mmap
  pages touched and bytes bound for the device (folded in from
  ``AccessStats``), the measured host/device *overlap fraction*, and
  the process's JAX compiles — surfaced through
  ``RetrievalServer.health()`` and ``benchmarks/bench_latency.py``;
* every stage run is also a ``stage:<name>`` profiler span
  (``jax.profiler.TraceAnnotation``) over the same interval as its
  recorded wall time, carrying the batch's request ids (``qids``), so
  a profile of the server names what the host did between the
  device's operations, per request.

Running a plan synchronously (``StagePlan.run``) and through the
executor are the *same stage functions in the same order*, so
``pipeline_depth=1`` (synchronous) vs ``>=2`` (pipelined) parity is
testable and holds bit-for-bit per method.

This module is a leaf: it imports nothing from ``repro.core`` so the
core retrievers can compile plans against it without cycles.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
import weakref
from concurrent.futures import Future
from types import MappingProxyType
from typing import Any, Callable, Mapping, Optional

import jax.monitoring
import numpy as np
from jax.profiler import TraceAnnotation

HOST = "host"
DEVICE = "device"

STAGE_KINDS = (HOST, DEVICE)

# the duration event JAX records once per program the backend compiles
# (a persistent-cache hit records none)
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def span_ids(qids) -> dict:
    """Profiler-span stats that join a batch-level span to its requests:
    ``qids``, the request ids joined by spaces (the profiler's metadata
    encoding splits a value at commas). Empty when the ids are unknown."""
    return {"qids": " ".join(map(str, qids))} if qids else {}


class PipelineStopped(RuntimeError):
    """Raised into futures whose CandidateBatch was still in flight (or
    still queued) when the executor stopped, and by ``submit`` on a
    stopped executor."""


# ---------------------------------------------------------------------------
# carrier
# ---------------------------------------------------------------------------

_EMPTY_STATE: Mapping[str, Any] = MappingProxyType({})


@dataclasses.dataclass(frozen=True)
class CandidateBatch:
    """Immutable carrier passed between stages.

    Stages never mutate a batch: they return a new instance via
    :meth:`evolve` / :meth:`with_state`, so a half-processed micro-batch
    can sit in a queue while the producing stage moves on to the next
    one without aliasing hazards. ``state`` holds named intermediate
    products (candidate sets, gathered codes/residuals, device scores);
    ``pids``/``scores`` are the final per-query results filled in by the
    terminal ``fuse_topk`` stage.

    ``shard_states`` is the batch's *shard axis*: under a sharded index
    (scatter-gather serving) each fanout stage writes one state mapping
    per shard, read back by the next fanout stage (same shard slot) or
    by a ``merge_topk`` fuse that combines per-shard candidates into
    global results.
    """

    method: str
    k: int
    q_embs: Optional[tuple] = None          # per-query (Lq_i, d) arrays
    term_ids: Optional[tuple] = None        # per-query (Qt_i,) arrays
    term_weights: Optional[tuple] = None
    alphas: Optional[np.ndarray] = None     # (B,) hybrid interpolation
    ctxs: Optional[tuple] = None            # per-query RequestContext
    qids: Optional[tuple] = None            # request ids (profiler spans)
    state: Mapping[str, Any] = _EMPTY_STATE
    shard_states: Optional[tuple] = None    # per-shard state mappings
    pids: Optional[np.ndarray] = None       # (B, k) final, -1 padded
    scores: Optional[np.ndarray] = None     # (B, k) final, desc

    @property
    def n_queries(self) -> int:
        for seq in (self.q_embs, self.term_ids):
            if seq is not None:
                return len(seq)
        return 0

    def evolve(self, **fields) -> "CandidateBatch":
        return dataclasses.replace(self, **fields)

    def with_state(self, **kv) -> "CandidateBatch":
        merged = dict(self.state)
        merged.update(kv)
        return dataclasses.replace(self, state=MappingProxyType(merged))


# ---------------------------------------------------------------------------
# stages and plans
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Stage:
    """One typed step of a plan. ``kind`` declares what the stage binds
    on (``host``: mmap gathers / numpy passes; ``device``: jitted or
    Pallas dispatches) — the executor uses it for worker placement in
    threaded mode, overlap accounting, and AccessStats attribution.

    ``opens_async`` marks a stage whose device dispatch returns *lazy*
    values (the async window opens when the stage ends);
    ``closes_async`` marks the downstream stage whose first host touch
    blocks on those values (the window closes when it starts). The
    single-worker scheduler parks a batch at its ``closes_async`` stage
    while younger batches still have pre-sync stages to run — software
    pipelining that hides device execution behind the next batch's host
    work without any thread (or GIL) contention.

    ``fanout > 0`` declares a *sharded* stage: ``fn`` has the signature
    ``fn(cb, shard) -> Mapping`` and runs once per shard, each
    invocation returning that shard's new state mapping; the executor
    assembles the results into ``cb.shard_states``. With ``pooled``
    (and a plan ``pool``) the per-shard calls run concurrently on
    threads — profitable exactly when the per-shard body releases the
    GIL, i.e. the mmap ``host_gather`` stages (big fancy-index copies
    and page faults overlap; this is the scatter half of scatter-gather
    serving). Device fanout stages leave ``pooled`` off: their
    dispatches are async already — shard i's accelerator crunches while
    shard i+1 is being dispatched — and pushing the GIL-bound Python
    dispatch overhead onto competing threads only serialises it with
    extra context switches."""

    name: str                                  # unique within the plan
    kind: str                                  # HOST | DEVICE
    fn: Callable[..., Any]
    opens_async: bool = False
    closes_async: bool = False
    fanout: int = 0                            # >0: per-shard execution
    pooled: bool = False                       # fanout via the plan pool
    device_dispatches: Optional[int] = None    # declared launches/run

    def __post_init__(self):
        if self.kind not in STAGE_KINDS:
            raise ValueError(f"stage kind {self.kind!r} not in "
                             f"{STAGE_KINDS}")

    @property
    def device_dispatch_count(self) -> int:
        """Device computations this stage launches per execution —
        declared at plan-build time (jitted calls plus eager jnp ops,
        each a separate XLA dispatch), defaulting to 1 for device
        stages and 0 for host stages. This is what makes the fused
        rerank tail's dispatch reduction *visible*: the split tail
        declares 3-4 launches per batch, the fused stage declares 1."""
        if self.device_dispatches is not None:
            return self.device_dispatches
        return 1 if self.kind == DEVICE else 0


@dataclasses.dataclass(frozen=True)
class StagePlan:
    """An ordered stage graph for one retrieval method.

    ``access_stats``, when set (the mmap store's ``AccessStats``), is
    snapshotted around host-kind stages so pages/tokens touched are
    attributed per stage. Under concurrent execution two host stages of
    different in-flight batches can interleave gathers, so per-stage
    page attribution is approximate there; totals stay exact.

    ``pool`` (duck-typed: needs ``.map``) runs the per-shard calls of
    ``fanout`` stages concurrently — a ThreadPoolExecutor sized to the
    shard group in sharded serving; ``None`` degrades to sequential
    per-shard execution (correct, just unoverlapped).
    """

    method: str
    stages: tuple
    access_stats: Any = None   # duck-typed: needs .snapshot() -> dict
    pool: Any = None           # duck-typed: needs .map (fanout stages)

    def stage_names(self) -> tuple:
        return tuple(s.name for s in self.stages)

    def run_stage(self, stage: Stage, cb: CandidateBatch,
                  stats: Optional["PipelineStats"] = None
                  ) -> CandidateBatch:
        """Run one stage as a ``stage:<name>`` profiler span; with
        ``stats``, record its wall time (the span's interval), and for
        host stages the mmap pages and device-bound bytes it gathered
        (also set as the span's ``h2d_bytes``)."""
        acc = self.access_stats if stage.kind == HOST else None
        before = acc.snapshot() if acc is not None else None
        if stats is not None:
            if stage.closes_async:
                stats.async_close()
            stats.stage_begin()
        with TraceAnnotation(f"stage:{stage.name}",
                             **span_ids(cb.qids)) as span:
            t0 = time.perf_counter()
            try:
                out = self._call_stage(stage, cb)
            finally:
                wall = time.perf_counter() - t0
                if stats is not None:
                    stats.stage_end()
            pages = h2d = 0
            if before is not None:
                after = acc.snapshot()
                pages = after["pages_touched"] - before["pages_touched"]
                h2d = after["h2d_bytes"] - before["h2d_bytes"]
                span.set_metadata(h2d_bytes=h2d)
        if stats is not None:
            if stage.opens_async:
                stats.async_open()
            stats.record(stage.name, wall, queries=cb.n_queries,
                         pages_touched=pages, h2d_bytes=h2d,
                         device_dispatches=stage.device_dispatch_count
                         * max(1, stage.fanout))
        return out

    def _call_stage(self, stage: Stage, cb: CandidateBatch):
        """Dispatch one stage: plain stages run ``fn(cb)``; fanout
        stages run ``fn(cb, shard)`` once per shard — on the shard pool
        when available — and assemble the returned mappings into the
        batch's shard axis. A shard that raises fails the whole batch
        (scatter-gather has no partial answers), but only this batch:
        the executor resolves its future with the error and the other
        in-flight batches proceed."""
        if not stage.fanout:
            return stage.fn(cb)
        shards = range(stage.fanout)
        if stage.pooled and self.pool is not None:
            outs = list(self.pool.map(lambda i: stage.fn(cb, i), shards))
        else:
            outs = [stage.fn(cb, i) for i in shards]
        return cb.evolve(shard_states=tuple(outs))

    def run(self, cb: CandidateBatch,
            stats: Optional["PipelineStats"] = None) -> CandidateBatch:
        """Synchronous execution — the ``pipeline_depth=1`` path. Same
        stage functions, same order as the pipelined executor.

        A batch that dies between its ``opens_async`` and
        ``closes_async`` stages (a failed device sync, a shard worker
        crashing under its score RPC) must balance the async window on
        the way out — the executor does this in ``_finish``; here the
        raise path does it — or the shared overlap accounting would
        count "dispatch in flight" forever after one failure."""
        window_open = False
        try:
            for stage in self.stages:
                if stage.closes_async:
                    window_open = False    # run_stage closes it up front
                cb = self.run_stage(stage, cb, stats)
                if stage.opens_async:
                    window_open = True
            return cb
        except BaseException:
            if window_open and stats is not None:
                stats.async_close()
            raise


# ---------------------------------------------------------------------------
# instrumentation: the merged stage_stats + AccessStats record
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class StageRecord:
    wall_s: float = 0.0
    dispatches: int = 0
    queries: int = 0
    pages_touched: int = 0
    h2d_bytes: int = 0                   # gathered bytes bound for device
    device_dispatches: int = 0           # declared device launches
    ewma_ms: Optional[float] = None      # EWMA of per-dispatch wall time

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class PipelineStats:
    """Thread-safe per-stage instrumentation shared by the synchronous
    path and every stage worker.

    Overlap accounting: workers bracket each stage with
    ``stage_begin``/``stage_end``, and lazy device dispatches open an
    *async window* (``async_open`` when the dispatching stage ends,
    ``async_close`` when the consuming sync stage starts). Time accrues
    to ``overlap_s`` whenever >= 2 stages execute simultaneously
    (threaded overlap) **or** a stage executes while a device dispatch
    is in flight (software pipelining: the device computes on its own
    execution thread while the host runs another batch's stage). The
    *overlap fraction* — overlapped time over any-stage-busy time — is
    the pipeline's win: 0.0 when execution is strictly serial (depth 1
    runs the sync stage immediately after the dispatch), > 0 when
    gathers and device scoring actually ran concurrently.

    Compiles: every instance counts the programs JAX compiles in this
    process (``jax_compiles``, and their ``jax_compile_ms``) among its
    counters, from one ``jax.monitoring`` listener per process. A
    served window after warm-up should count none.

    Gauges (:meth:`gauge`) add values of the moment to the counters of
    every snapshot, such as a live index's delta size.
    """

    def __init__(self, ewma_alpha: float = 0.25):
        self._lock = threading.Lock()
        self._ewma_alpha = ewma_alpha
        self._stages: dict[str, StageRecord] = {}
        self._busy = 0
        self._async = 0
        self._t_mark: Optional[float] = None
        self._busy_any_s = 0.0
        self._overlap_s = 0.0
        self._counters: dict[str, float] = {}
        self._gauges: list[Callable[[], Mapping[str, float]]] = []
        _watch_compiles(self)

    def reset(self):
        with self._lock:
            self._stages.clear()
            self._busy = 0
            self._async = 0
            self._t_mark = None
            self._busy_any_s = 0.0
            self._overlap_s = 0.0
            self._counters.clear()

    # -- overlap ---------------------------------------------------------
    def _tick(self, now: float):
        if self._t_mark is not None and self._busy > 0:
            dt = now - self._t_mark
            self._busy_any_s += dt
            if self._busy >= 2 or self._async >= 1:
                self._overlap_s += dt
        self._t_mark = now

    def stage_begin(self):
        with self._lock:
            self._tick(time.perf_counter())
            self._busy += 1

    def stage_end(self):
        with self._lock:
            self._tick(time.perf_counter())
            self._busy = max(0, self._busy - 1)

    def async_open(self):
        """A device dispatch went in flight (lazy results outstanding)."""
        with self._lock:
            self._tick(time.perf_counter())
            self._async += 1

    def async_close(self):
        """The consuming stage is about to block on those results."""
        with self._lock:
            self._tick(time.perf_counter())
            self._async = max(0, self._async - 1)

    # -- records ---------------------------------------------------------
    def record(self, name: str, wall_s: float, *,
               queries: int = 0, dispatches: int = 1,
               pages_touched: int = 0, h2d_bytes: int = 0,
               device_dispatches: int = 0):
        with self._lock:
            rec = self._stages.get(name)
            if rec is None:
                rec = self._stages[name] = StageRecord()
            rec.wall_s += wall_s
            rec.dispatches += dispatches
            rec.queries += queries
            rec.pages_touched += pages_touched
            rec.h2d_bytes += h2d_bytes
            rec.device_dispatches += device_dispatches
            ms = wall_s * 1e3
            rec.ewma_ms = (ms if rec.ewma_ms is None
                           else self._ewma_alpha * ms
                           + (1 - self._ewma_alpha) * rec.ewma_ms)

    def counter(self, name: str, delta: float = 1):
        """Bump a named monotonic counter (transport bytes, RPC
        dispatches, coalesced-op counts, …); surfaced in
        :meth:`snapshot` under ``"counters"``."""
        with self._lock:
            self._counters[name] = self._counters.get(name, 0) + delta

    def gauge(self, read: Callable[[], Mapping[str, float]]):
        """Add ``read()``'s values to the counters of every later
        snapshot, as they stand when it is taken; kept across
        :meth:`reset`."""
        with self._lock:
            self._gauges.append(read)

    def snapshot(self) -> dict:
        """Atomic copy: {"stages": {name: record-dict},
        "overlap_fraction": ..., "counters": ...}; the gauges are read
        after the copy."""
        with self._lock:
            stages = {n: r.as_dict() for n, r in self._stages.items()}
            busy, over = self._busy_any_s, self._overlap_s
            counters = dict(self._counters)
            gauges = list(self._gauges)
        for read in gauges:
            counters.update(read())
        return {"stages": stages,
                "overlap_fraction": over / busy if busy > 0 else 0.0,
                "counters": counters}


# the process's one compile listener, fanned out to every live
# PipelineStats (JAX keeps listeners for the life of the process)
_compile_lock = threading.Lock()
_compile_sinks: "weakref.WeakSet[PipelineStats]" = weakref.WeakSet()
_compile_listening = False


def _on_duration(event: str, duration_s: float, **_):
    if event != COMPILE_EVENT:
        return
    with _compile_lock:
        sinks = list(_compile_sinks)
    for stats in sinks:
        stats.counter("jax_compiles")
        stats.counter("jax_compile_ms", duration_s * 1e3)


def _watch_compiles(stats: PipelineStats):
    """Count the process's compiles into ``stats`` from now on."""
    global _compile_listening
    with _compile_lock:
        if not _compile_listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
            _compile_listening = True
        _compile_sinks.add(stats)


# ---------------------------------------------------------------------------
# executor
# ---------------------------------------------------------------------------

class _Job:
    __slots__ = ("cb", "future", "idx", "async_open")

    def __init__(self, cb: CandidateBatch, future: Future):
        self.cb = cb
        self.future = future
        self.idx = 0                       # next stage to run
        self.async_open = False            # opened an unclosed async window


WORKER_MODES = ("single", "kind")


class PipelineExecutor:
    """Runs a :class:`StagePlan` with ``depth`` micro-batches in flight.

    ``submit`` feeds the pipeline head and returns a Future resolved at
    the tail with the finished :class:`CandidateBatch`. At most
    ``depth`` batches are admitted: when the pipeline is full,
    ``submit`` *blocks* — producers are backpressured and in-flight
    memory is bounded. ``depth=2`` double-buffers: batch N's device
    scoring executes while batch N+1's host gather runs.

    Two scheduling modes (``workers``):

    * ``"single"`` (default) — one worker thread, software-pipelined:
      it runs every stage, but *parks* a batch at its ``closes_async``
      stage (the device-result sync) while younger batches still have
      pre-sync stages, so the device — whose dispatches are async and
      execute on the runtime's own (GIL-free) threads — crunches batch
      N while the worker gathers batch N+1. Measured on 2-core hosts
      this beats threaded stage workers, whose ms-scale GIL-holding
      numpy sections stall each other harder than the overlap pays.
    * ``"kind"`` — one worker per stage *kind* (host-gather worker +
      device-dispatch worker) connected by queues; worthwhile when host
      stages release the GIL for real work (large mmap fault storms,
      multi-core hosts, hardware accelerators with slow host syncs).
      Kind-based FIFO hand-off cannot deadlock: queue occupancy is
      capped by the admission semaphore.

    ``stop()`` fails still-queued batches with :class:`PipelineStopped`;
    the batch a worker is mid-stage on finishes that stage and then
    fails (or resolves, if it was the last stage) — every submitted
    future resolves or fails, none hang.
    """

    def __init__(self, plan: StagePlan, depth: int = 2,
                 stats: Optional[PipelineStats] = None,
                 name: Optional[str] = None, workers: str = "single"):
        if not plan.stages:
            raise ValueError("empty StagePlan")
        if workers not in WORKER_MODES:
            raise ValueError(f"workers {workers!r} not in {WORKER_MODES}")
        self.plan = plan
        self.depth = max(1, int(depth))
        self.stats = stats
        self.mode = workers
        self.running = True
        self._sem = threading.Semaphore(self.depth)   # admission permits
        self._cond = threading.Condition()
        self._inflight = 0
        self._qlock = threading.Lock()
        self._queued = {st.name: 0 for st in plan.stages}
        label = name or plan.method
        self.workers: list[threading.Thread] = []
        if workers == "single":
            self._intake: queue.Queue = queue.Queue()
            t = threading.Thread(target=self._worker_single,
                                 name=f"pipe-{label}", daemon=True)
            t.start()
            self.workers.append(t)
        else:
            kinds = list(dict.fromkeys(st.kind for st in plan.stages))
            self._queues = {kind: queue.Queue() for kind in kinds}
            for kind in kinds:
                t = threading.Thread(target=self._worker_kind, args=(kind,),
                                     name=f"pipe-{label}-{kind}",
                                     daemon=True)
                t.start()
                self.workers.append(t)

    # -- producer side ---------------------------------------------------
    def submit(self, cb: CandidateBatch) -> Future:
        if not self.running:
            raise PipelineStopped("executor is stopped")
        while not self._sem.acquire(timeout=0.05):   # backpressure
            if not self.running:
                raise PipelineStopped("executor stopped")
        if not self.running:
            self._sem.release()
            raise PipelineStopped("executor stopped")
        fut: Future = Future()
        fut.set_running_or_notify_cancel()   # internal: never cancelled
        job = _Job(cb, fut)
        with self._cond:
            self._inflight += 1
        self._mark_queued(job.idx, +1)
        if self.mode == "single":
            self._intake.put(job)
        else:
            self._queues[self.plan.stages[job.idx].kind].put(job)
        if not self.running:
            # raced stop(): its drain may already have passed this queue,
            # so drain again — get_nowait makes each job fail exactly once
            self._fail_queued()
        return fut

    def _mark_queued(self, idx: int, delta: int):
        with self._qlock:
            self._queued[self.plan.stages[idx].name] += delta

    # -- single-worker software pipelining -------------------------------
    def _next_job(self, jobs: list) -> "_Job":
        """Lookahead schedule: advance the oldest batch that is NOT
        parked at its device-result sync; if every admitted batch is
        parked (or there is just one), advance the oldest — by then its
        device results have had the younger batches' host stages to
        complete. Plans without ``closes_async`` stages degrade to plain
        FIFO."""
        for job in jobs:
            if not self.plan.stages[job.idx].closes_async:
                return job
        return jobs[0]

    def _admit(self, jobs: list):
        """Admit available batches. When every admitted batch is parked
        at its device-result sync (and there is admission room), wait a
        moment for fresh work before blocking on a sync: under load the
        producer's next batch arrives within microseconds, and running
        its host stages first keeps the parked batches' device work
        hidden — without this, depth=2 syncs too eagerly and exposes
        the execute it just dispatched."""
        while True:
            if not jobs:
                block, timeout = True, 0.05
            elif (len(jobs) < self.depth
                  and all(self.plan.stages[j.idx].closes_async
                          for j in jobs)):
                block, timeout = True, 0.002
            else:
                block, timeout = False, None
            try:
                jobs.append(self._intake.get(block=block, timeout=timeout))
            except queue.Empty:
                return

    def _worker_single(self):
        jobs: list[_Job] = []
        while True:
            self._admit(jobs)
            if not jobs:
                if not self.running:
                    return
                continue
            if not self.running:
                for job in jobs:
                    self._mark_queued(job.idx, -1)
                    self._finish(job, exc=PipelineStopped(
                        "executor stopped mid-flight"))
                jobs.clear()
                continue
            job = self._next_job(jobs)
            if self._advance(job):
                jobs.remove(job)

    # -- shared stage step -----------------------------------------------
    def _advance(self, job: _Job) -> bool:
        """Run the job's next stage on the calling worker (queued-count
        and async-window bookkeeping included). Returns True
        when the job left the pipeline (finished or failed); False when
        it advanced to the next stage — already marked queued, but not
        yet handed to a worker queue."""
        stage = self.plan.stages[job.idx]
        self._mark_queued(job.idx, -1)
        if stage.closes_async:
            job.async_open = False         # run_stage closes the window
        try:
            cb = self.plan.run_stage(stage, job.cb, self.stats)
        except Exception as e:
            self._finish(job, exc=e)
            return True
        if stage.opens_async:
            job.async_open = True
        job.idx += 1
        if job.idx == len(self.plan.stages):
            self._finish(job, cb=cb)
            return True
        job.cb = cb
        self._mark_queued(job.idx, +1)
        return False

    # -- kind-threaded workers -------------------------------------------
    def _worker_kind(self, kind: str):
        q = self._queues[kind]
        while True:
            try:
                job = q.get(timeout=0.05)
            except queue.Empty:
                if not self.running:
                    return
                continue
            if not self.running:
                self._mark_queued(job.idx, -1)
                self._finish(job, exc=PipelineStopped(
                    "executor stopped before stage "
                    f"{self.plan.stages[job.idx].name!r}"))
                continue
            if not self._advance(job):
                self._queues[self.plan.stages[job.idx].kind].put(job)
                if not self.running:
                    # raced stop(): a worker that outlived the join (a
                    # long mid-stage gather) must not strand the job in
                    # a queue nobody reads — drain-and-fail it now
                    self._fail_queued()

    def _finish(self, job: _Job, cb: Optional[CandidateBatch] = None,
                exc: Optional[BaseException] = None):
        if job.async_open and self.stats is not None:
            # the batch dies between its opens_async and closes_async
            # stages (stage error / shutdown): balance the window so the
            # shared overlap accounting cannot stick at "in flight"
            job.async_open = False
            self.stats.async_close()
        if exc is not None:
            job.future.set_exception(exc)
        else:
            job.future.set_result(cb)
        self._sem.release()
        with self._cond:
            self._inflight -= 1
            self._cond.notify_all()

    # -- lifecycle / introspection --------------------------------------
    def has_room(self) -> bool:
        """Fewer than ``depth`` batches admitted: a ``submit`` now would
        not block on backpressure."""
        with self._cond:
            return self._inflight < self.depth

    def drain(self, timeout: Optional[float] = None) -> bool:
        """Wait until no batches are in flight."""
        with self._cond:
            return self._cond.wait_for(lambda: self._inflight == 0,
                                       timeout)

    def queue_depths(self) -> dict:
        """Batches currently waiting per stage (not executing)."""
        with self._qlock:
            return dict(self._queued)

    def stop(self):
        """Stop workers; every in-flight future resolves (if its last
        stage already ran) or fails with :class:`PipelineStopped`."""
        self.running = False
        for t in self.workers:
            t.join(timeout=5.0)
        self.workers.clear()
        self._fail_queued()

    def _fail_queued(self):
        """Fail whatever still sits in the queues (shared by ``stop``
        and a ``submit`` that raced it; ``get_nowait`` guarantees each
        job is finished exactly once)."""
        leftovers = ([self._intake] if self.mode == "single"
                     else list(self._queues.values()))
        for q in leftovers:
            while True:
                try:
                    job = q.get_nowait()
                except queue.Empty:
                    break
                self._mark_queued(job.idx, -1)
                self._finish(job, exc=PipelineStopped(
                    "executor stopped with the batch still queued"))


def gather_futures(futs: list) -> Future:
    """Aggregate Futures into one resolving with the list of results
    (in order) once all complete, or failing with the first exception."""
    out: Future = Future()
    out.set_running_or_notify_cancel()
    if not futs:
        out.set_result([])
        return out
    remaining = [len(futs)]
    lock = threading.Lock()

    def on_done(_f):
        with lock:
            remaining[0] -= 1
            if remaining[0]:
                return
        for f in futs:
            e = f.exception()
            if e is not None:
                out.set_exception(e)
                return
        out.set_result([f.result() for f in futs])

    for f in futs:
        f.add_done_callback(on_done)
    return out
