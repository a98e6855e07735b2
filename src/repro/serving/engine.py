"""Scoring engine: the per-request work unit behind the server.

The paper's concurrency fix was releasing the GIL around ColBERT's C++
extensions; in this stack the same property holds natively — JAX device
dispatch releases the GIL, so a thread pool scales until the backend
saturates. The engine is stateless per request and thread-safe: all
mutable state (page-cache stats) is guarded or append-only.

With ``pipeline_depth >= 2`` the engine executes micro-batches through
the stage-graph pipeline (`repro.serving.pipeline`): each method's
compiled :class:`StagePlan` runs on per-stage workers connected by
bounded queues, so micro-batch N+1's host mmap gather overlaps
micro-batch N's device dispatch. ``process_batch_async`` feeds the
pipeline head and returns a Future resolved at the tail;
``pipeline_depth=1`` (default) keeps the synchronous path.
"""

from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation

from repro.core.multistage import MultiStageRetriever
from repro.serving.context import (
    ADMIT_DEGRADED,
    ADMIT_FULL,
    CacheHierarchy,
    RequestContext,
    exact_cache_key,
    freeze,
    query_digest,
    stage1_cache_key,
)
from repro.serving.pipeline import (
    PipelineExecutor,
    PipelineStopped,
    gather_futures,
    span_ids,
)


@dataclasses.dataclass
class Request:
    qid: int
    method: str                      # colbert | splade | rerank | hybrid
    q_emb: Optional[np.ndarray] = None
    term_ids: Optional[np.ndarray] = None
    term_weights: Optional[np.ndarray] = None
    k: int = 100
    alpha: Optional[float] = None
    t_arrival: float = 0.0
    deadline_ms: Optional[float] = None   # per-request latency budget
    trace_id: Optional[int] = None        # load-trace identity (repeats)
    # typed lifecycle record (cache keys, admission class); built by
    # the engine/server on demand, carried with the request after that
    ctx: Optional[RequestContext] = None


@dataclasses.dataclass
class Result:
    qid: int
    pids: np.ndarray
    scores: np.ndarray
    t_arrival: float
    t_start: float
    t_done: float
    # degraded answers: shard groups missing replicas, or admission
    # control downgrading the request to the splade-only plan; the
    # reason code says which
    degraded: bool = False
    missing_shards: tuple = ()
    degrade_reason: str = ""
    cache_hit: bool = False

    @property
    def latency(self) -> float:
        """Client-observed latency (includes queueing) — what the paper
        reports at p95."""
        return self.t_done - self.t_arrival

    @property
    def service_time(self) -> float:
        return self.t_done - self.t_start


class ServeEngine:
    def __init__(self, retriever: MultiStageRetriever,
                 pipeline_depth: int = 1,
                 pipeline_workers: str = "single",
                 own_retriever: bool = False,
                 caches: Optional[CacheHierarchy] = None):
        """The retriever owns the stage-1 scorer
        (``set_splade_backend``); the engine serves whatever it is set to.

        ``pipeline_depth``: 1 = synchronous batches (classic path);
        >= 2 = stage-graph pipelining with that many batches in flight
        (2 = double-buffered). ``pipeline_workers``: executor scheduling
        mode — ``"single"`` (software pipelining; default) or ``"kind"``
        (host/device worker threads; see ``PipelineExecutor``).
        Pipelining needs a retriever that can ``compile_plan``; others
        silently stay synchronous.

        ``own_retriever=True`` transfers the retriever's lifecycle to
        this engine: ``close()`` also calls ``retriever.close()`` when
        it has one. Launchers set it so a process-shard group's worker
        processes are reaped on every exit path (no orphans); leave it
        False when the retriever is shared across engines.

        ``caches``: optional :class:`CacheHierarchy`. The exact result
        cache is consulted/filled by the engine itself; the stage-1
        cache is attached to the retriever, whose plans consult it via
        the per-request contexts threaded through ``build_batch``."""
        self.retriever = retriever
        self._own_retriever = own_retriever
        self.caches = caches
        if caches is not None and hasattr(retriever, "attach_caches"):
            retriever.attach_caches(caches)
        self.pipeline_depth = max(1, pipeline_depth)
        self.pipeline_workers = pipeline_workers
        self._pipelines: dict = {}
        self._plock = threading.Lock()
        self._closed = False
        self._lock = threading.Lock()
        self.served = 0

    # -- pipelining ------------------------------------------------------
    @property
    def pipelined(self) -> bool:
        # a live (mutable) retriever must stay synchronous: pipelined
        # executors hold compiled plans across batches, and a mutation
        # or compaction swap mid-flight would race the stage graph
        return (self.pipeline_depth > 1
                and hasattr(self.retriever, "compile_plan")
                and getattr(self.retriever, "live", None) is None)

    def _pipeline(self, method: str) -> PipelineExecutor:
        """Per-method executor over the method's compiled plan, built
        lazily and rebuilt if the plan changed (e.g. stage-1 backend
        switch recompiles the plan). The stale executor is stopped
        OUTSIDE the registry lock — stop() joins worker threads, and
        holding ``_plock`` across that would stall health() and every
        concurrent dispatch."""
        plan = self.retriever.compile_plan(method)   # validates method
        stale = None
        try:
            with self._plock:
                if self._closed:
                    raise PipelineStopped("engine closed")
                px = self._pipelines.get(method)
                if px is not None and (px.plan is not plan
                                       or not px.running):
                    stale, px = px, None
                if px is None:
                    px = PipelineExecutor(
                        plan, depth=self.pipeline_depth,
                        stats=self.retriever.pipeline_stats,
                        workers=self.pipeline_workers)
                    self._pipelines[method] = px
                return px
        finally:
            if stale is not None and stale.running:
                stale.stop()

    def can_admit(self, reqs: list[Request]) -> bool:
        """Whether a micro-batch of ``reqs`` would start at once: always
        on the synchronous path (the worker that formed the batch serves
        it); pipelined, while every executor the batch feeds has fewer
        than ``pipeline_depth`` batches admitted."""
        if not self.pipelined:
            return True
        with self._plock:
            pipes = [self._pipelines.get(m) for m in
                     {self._effective_method(r) for r in reqs}]
        return all(px is None or px.has_room() for px in pipes)

    def drain_pipelines(self, timeout: Optional[float] = None):
        for px in list(self._pipelines.values()):
            px.drain(timeout)

    def stop_pipelines(self):
        """Stop the stage workers; in-flight micro-batches resolve or
        fail their futures (PipelineStopped). The engine stays usable —
        the next pipelined batch lazily rebuilds its executor — so a
        server can stop()/start() (or a new server can reuse the
        engine) without being wedged."""
        with self._plock:
            pipes = list(self._pipelines.values())
            self._pipelines.clear()
        for px in pipes:
            px.stop()

    def close(self):
        """stop_pipelines() + refuse to build new executors. Terminal.
        An engine that owns its retriever shuts it down too (a process
        shard group terminates and reaps its worker processes here)."""
        with self._plock:
            self._closed = True
        self.stop_pipelines()
        if self._own_retriever and hasattr(self.retriever, "close"):
            self.retriever.close()

    def pipeline_health(self) -> dict:
        """Executor-specific vitals: queue depths per stage, per method.
        (Per-stage timing/pages/overlap live in the retriever's
        ``pipeline_stats`` snapshot, which ``RetrievalServer.health``
        reports — not duplicated here.)"""
        with self._plock:            # _pipeline() inserts concurrently
            pipes = dict(self._pipelines)
        return {"depth": self.pipeline_depth,
                "queues": {m: px.queue_depths()
                           for m, px in pipes.items()}}

    # -- live index ------------------------------------------------------
    def live_upsert(self, doc_emb, term_ids, term_weights,
                    doc_len=None) -> int:
        """Append one document to the retriever's delta segment; returns
        the new global pid. Requires ``enable_live()`` on the
        retriever."""
        return self.retriever.live_upsert(doc_emb, term_ids, term_weights,
                                          doc_len)

    def live_delete(self, pid: int) -> bool:
        return self.retriever.live_delete(pid)

    def live_compact(self):
        return self.retriever.compact_live()

    def live_stats(self):
        live = getattr(self.retriever, "live", None)
        if live is None:
            return None
        return self.retriever.live_stats()

    # -- request context & caching ---------------------------------------
    def context_for(self, req: Request) -> RequestContext:
        """Resolve a request into its typed lifecycle record.

        Cache keys are built from exact byte digests of the request's
        tensors plus the retriever's config salt; they stay ``None``
        when the engine has no caches (or the retriever can't salt
        them), which disables every cache path for that request."""
        retr = self.retriever
        alpha = req.alpha
        if alpha is None:
            alpha = getattr(getattr(retr, "params", None), "alpha", None)
        cache_key = stage1_key = None
        salts = getattr(retr, "cache_salts", None)
        if (salts is not None and self.caches is not None
                and self.caches.enabled):
            exact_salt, stage1_salt = salts(req.method)
            digest = query_digest(req.q_emb, req.term_ids,
                                  req.term_weights)
            cache_key = exact_cache_key(digest, req.method, req.k,
                                        alpha, exact_salt)
            if req.method == "colbert":
                s1_digest = query_digest(req.q_emb, None, None)
            else:
                s1_digest = query_digest(None, req.term_ids,
                                         req.term_weights)
            stage1_key = stage1_cache_key(s1_digest, stage1_salt)
        return RequestContext(
            qid=req.qid, method=req.method, k=req.k, alpha=alpha,
            t_arrival=req.t_arrival, deadline_ms=req.deadline_ms,
            cache_key=cache_key, stage1_key=stage1_key)

    def _ensure_ctxs(self, reqs: list[Request]) -> None:
        if self.caches is None or not self.caches.enabled:
            return
        for r in reqs:
            if r.ctx is None:
                r.ctx = self.context_for(r)

    def cache_lookup(self, req: Request,
                     count_miss: bool = True) -> Optional[Result]:
        """Exact-cache probe; a hit IS the answer (bitwise the cold
        result) and counts as served. ``count_miss=False`` for
        advisory probes (the server's submit fast path) so the
        process-time probe stays the authoritative miss count."""
        caches = self.caches
        if caches is None or caches.exact.capacity <= 0:
            return None
        if req.ctx is None:
            req.ctx = self.context_for(req)
        hit = caches.exact.get(req.ctx.cache_key, count_miss=count_miss)
        if hit is None:
            return None
        pids, scores = hit
        now = time.perf_counter()
        with self._lock:
            self.served += 1
        return Result(qid=req.qid, pids=pids, scores=scores,
                      t_arrival=req.t_arrival, t_start=now, t_done=now,
                      cache_hit=True)

    def _cache_store(self, req: Request, res: Result) -> None:
        """Fill the exact cache from a full-quality answer. Degraded
        answers (missing shards or admission downgrade) are never
        stored — a later healthy run of the same query must not be
        served yesterday's partial result."""
        caches = self.caches
        ctx = req.ctx
        if (caches is None or caches.exact.capacity <= 0
                or ctx is None or ctx.cache_key is None
                or res.degraded or res.cache_hit
                or ctx.admission != ADMIT_FULL):
            return
        caches.exact.put(ctx.cache_key, freeze(res.pids, res.scores),
                         getattr(self.retriever, "index_generation", 0))

    @staticmethod
    def _effective_method(req: Request) -> str:
        """Admission-degraded hybrid/rerank requests run the cheap
        splade-only plan; everything else keeps its own method."""
        ctx = req.ctx
        if (ctx is not None and ctx.admission == ADMIT_DEGRADED
                and req.method in ("hybrid", "rerank")
                and req.term_ids is not None and len(req.term_ids) > 0):
            return "splade"
        return req.method

    @staticmethod
    def _degrade_info(req: Request, missing: tuple) -> tuple:
        ctx = req.ctx
        adm = ctx is not None and ctx.admission == ADMIT_DEGRADED
        degraded = bool(missing) or adm
        reason = (ctx.admit_reason if adm
                  else ("missing_shards" if missing else ""))
        return degraded, reason

    # -- request execution -----------------------------------------------
    def process(self, req: Request) -> Result:
        """One request: :meth:`process_batch` of one."""
        return self.process_batch([req])[0]

    def process_batch(self, reqs: list[Request]) -> list[Result]:
        """Score a micro-batch in one batched retriever call per method
        group, through the methods' compiled stage plans; a single
        request is a batch of one. Requests keep their own
        ``k``/``alpha``.

        Cache hits are peeled off first; only the misses run the
        retriever."""
        self._ensure_ctxs(reqs)
        results: list = [None] * len(reqs)
        miss_idx = []
        for i, r in enumerate(reqs):
            hit = self.cache_lookup(r)
            if hit is not None:
                results[i] = hit
            else:
                miss_idx.append(i)
        if not miss_idx:
            return results
        miss = [reqs[i] for i in miss_idx]

        t_start = time.perf_counter()
        alphas = [r.alpha for r in miss]
        pids, scores, outcome = self.retriever.search_batch_ctx(
            [self._effective_method(r) for r in miss],
            q_embs=[r.q_emb for r in miss],
            term_ids=[r.term_ids for r in miss],
            term_weights=[r.term_weights for r in miss],
            alpha=None if all(a is None for a in alphas) else alphas,
            k=max(r.k for r in miss), ctxs=[r.ctx for r in miss])
        missing = outcome.missing_shards
        t_done = time.perf_counter()
        with self._lock:
            self.served += len(miss)
        for j, r in enumerate(miss):
            degraded, reason = self._degrade_info(r, missing)
            res = Result(qid=r.qid, pids=pids[j][:r.k],
                         scores=scores[j][:r.k], t_arrival=r.t_arrival,
                         t_start=t_start, t_done=t_done,
                         degraded=degraded, missing_shards=missing,
                         degrade_reason=reason)
            self._cache_store(r, res)
            results[miss_idx[j]] = res
        return results

    def process_batch_async(self, reqs: list[Request]) -> Future:
        """Feed a micro-batch to the stage pipeline; the returned Future
        resolves with the ``list[Result]`` at the pipeline tail.

        Per-request results match :meth:`process_batch` exactly: a
        single-method batch runs its plan as one CandidateBatch; a
        mixed batch is grouped per method, each group submitted to its
        method's executor, and results scattered back into request
        order with the same prefix/padding semantics as the synchronous
        mixed path. ``submit`` blocks while the head queue is full, so
        callers are backpressured by ``pipeline_depth``."""
        if not self.pipelined:
            out: Future = Future()
            out.set_running_or_notify_cancel()
            try:
                out.set_result(self.process_batch(reqs))
            except Exception as e:
                out.set_exception(e)
            return out

        self._ensure_ctxs(reqs)
        hits: list = [None] * len(reqs)
        miss_idx = []
        for i, r in enumerate(reqs):
            hit = self.cache_lookup(r)
            if hit is not None:
                hits[i] = hit
            else:
                miss_idx.append(i)
        if not miss_idx:
            out = Future()
            out.set_running_or_notify_cancel()
            out.set_result(hits)
            return out
        miss = [reqs[i] for i in miss_idx]

        t_start = time.perf_counter()
        n = len(miss)
        k_max = max(r.k for r in miss)
        retr = self.retriever
        methods = [self._effective_method(r) for r in miss]
        raw_alphas = [r.alpha for r in miss]
        alphas = retr._alpha_array(
            None if all(a is None for a in raw_alphas) else raw_alphas, n)

        groups = []                      # (method, idx, CandidateBatch)
        for m in dict.fromkeys(methods):
            idx = [i for i, mi in enumerate(methods) if mi == m]
            cb = retr.build_batch(
                m,
                q_embs=[miss[i].q_emb for i in idx],
                term_ids=[miss[i].term_ids for i in idx],
                term_weights=[miss[i].term_weights for i in idx],
                alphas=alphas[idx], k=k_max,
                ctxs=[miss[i].ctx for i in idx],
                qids=[miss[i].qid for i in idx])
            groups.append((m, idx, cb))

        out: Future = Future()
        out.set_running_or_notify_cancel()
        futs = []
        try:
            # resolve every group's executor BEFORE submitting any work:
            # an unknown method then fails the batch without first
            # running (and throwing away) the valid groups' retrieval
            pipes = [self._pipeline(m) for m, _, _ in groups]
            for px, (_, _, cb) in zip(pipes, groups):
                futs.append(px.submit(cb))
        except Exception as e:
            # submit-time failure (unknown method, stopped pipeline):
            # fail the whole batch; the server retries request-by-request
            out.set_exception(e)
            return out

        agg = gather_futures(futs)

        def finish(f: Future):
            e = f.exception()
            if e is not None:
                out.set_exception(e)
                return
            try:
                with TraceAnnotation("stage:assemble", **span_ids(
                        [r.qid for r in miss])):
                    assembled = self._assemble(miss, groups, f.result(),
                                               n, k_max, t_start)
                full = hits
                for j, res in enumerate(assembled):
                    full[miss_idx[j]] = res
                out.set_result(full)
            except Exception as err:
                out.set_exception(err)

        agg.add_done_callback(finish)
        return out

    def _assemble(self, reqs, groups, cbs, n, k_max, t_start):
        missing: set = set()
        for cb in cbs:
            missing.update(cb.state.get("missing_shards", ()))
        missing = tuple(sorted(missing))
        if len(groups) == 1:
            pids, scores = cbs[0].pids, cbs[0].scores
        else:
            pids = np.full((n, k_max), -1, np.int64)
            scores = np.full((n, k_max), -np.inf, np.float32)
            for (_, idx, _), cb in zip(groups, cbs):
                MultiStageRetriever.scatter_group(pids, scores, idx,
                                                  cb.pids, cb.scores)
        t_done = time.perf_counter()
        with self._lock:
            self.served += n
        out = []
        for i, r in enumerate(reqs):
            degraded, reason = self._degrade_info(r, missing)
            res = Result(qid=r.qid, pids=pids[i][:r.k],
                         scores=scores[i][:r.k], t_arrival=r.t_arrival,
                         t_start=t_start, t_done=t_done,
                         degraded=degraded, missing_shards=missing,
                         degrade_reason=reason)
            self._cache_store(r, res)
            out.append(res)
        return out
