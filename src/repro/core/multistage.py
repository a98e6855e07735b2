"""ColBERT-serve's multi-stage retrieval pipeline.

Four systems, exactly as the paper's evaluation defines them:

  * ``colbert``  — full PLAID end-to-end (in-memory or MMAP per store mode)
  * ``splade``   — SPLADEv2 w/ PISA-style impact index only
  * ``rerank``   — SPLADE top-``first_k`` → MMAP ColBERT exact rescoring
  * ``hybrid``   — rerank + α-interpolated z-normed score fusion
"""

from __future__ import annotations

import contextlib
import dataclasses
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from repro.core import hybrid as hybrid_mod
from repro.core.plaid import (
    PLAIDSearcher,
    _pad_batch_rows,
    pad_query_batch,
    pad_query_batch_host,
)
from repro.index.splade_device import SpladeDeviceCache
from repro.index.splade_index import SpladeIndex
from repro.serving.context import BatchOutcome, freeze
from repro.serving.pipeline import (
    DEVICE,
    HOST,
    CandidateBatch,
    PipelineStats,
    Stage,
    StagePlan,
    span_ids,
)

SPLADE_BACKENDS = ("host", "jax", "pallas")
RERANK_BACKENDS = ("fused", "split")
METHODS = ("colbert", "splade", "rerank", "hybrid")


@dataclasses.dataclass(frozen=True)
class MultiStageParams:
    first_k: int = 200            # SPLADE candidates (paper: top-200)
    k: int = 100                  # final depth
    alpha: float = 0.3            # paper's MS MARCO-tuned value
    normalizer: str = "znorm"
    splade_backend: str = "host"  # stage-1 scorer: host | jax | pallas
    splade_max_df: Optional[int] = None  # padded-postings df cap (None=exact)
    rerank_backend: str = "fused"  # stage-4 tail: fused | split


class MultiStageRetriever:
    # coordinator cache hierarchy (attached by the engine) and the index
    # generation its entries are scoped to. Class-level defaults so the
    # sharded subclasses — which build themselves without calling this
    # __init__ — inherit a disabled-cache state for free.
    _caches = None
    index_generation: int = 0
    # live (mutable) index state: None = frozen serving (default; every
    # pre-live code path is untouched), a LiveIndexState on the owner
    # retriever, or a LiveView on shard-level / worker retrievers
    live = None

    def __init__(self, splade_index: SpladeIndex, searcher: PLAIDSearcher,
                 params: MultiStageParams = MultiStageParams(),
                 device=None):
        """``device`` (optional jax.Device) pins this retriever's
        device-resident stage-1 state — under a shard group each shard
        lands on its own mesh device (``launch.mesh.shard_device_map``)
        so per-shard dispatches execute in parallel."""
        self.splade = splade_index
        self.searcher = searcher
        self.params = params
        self.device = device
        self._splade_device: Optional[SpladeDeviceCache] = None
        self._lock = threading.Lock()
        self._plans: dict = {}
        # single per-stage instrumentation record (wall time, dispatches,
        # queue wait, mmap pages/tokens, overlap) — reset in place so
        # pipeline executors can hold a stable reference
        self.pipeline_stats = PipelineStats()
        self.set_splade_backend(params.splade_backend)  # validates
        self.set_rerank_backend(params.rerank_backend)
        self.reset_stage_stats()
        if params.splade_backend != "host":
            self.splade_device_cache()    # pay the transfer up front

    # ------------------------------------------------------------------
    # stage-1 backend selection
    # ------------------------------------------------------------------
    def set_splade_backend(self, backend: str):
        if backend not in SPLADE_BACKENDS:
            raise ValueError(f"splade backend {backend!r} not in "
                             f"{SPLADE_BACKENDS}")
        if backend != "host":
            self._splade_impl(backend)          # raises without a TPU
        self.splade_backend = backend

    def set_rerank_backend(self, backend: str):
        """Stage-4 tail selection: ``fused`` collapses exact scoring,
        masking, (hybrid) α-fusion and top-k selection into ONE device
        dispatch (the ``fused_rerank`` kernel / fused-XLA tail);
        ``split`` keeps the legacy multi-dispatch tail."""
        if backend not in RERANK_BACKENDS:
            raise ValueError(f"rerank backend {backend!r} not in "
                             f"{RERANK_BACKENDS}")
        self.rerank_backend = backend

    def splade_device_cache(self) -> SpladeDeviceCache:
        """Padded-postings device arrays, materialised once and reused
        across every jax/pallas stage-1 dispatch (locked: concurrent
        server workers must not each pay the host→device transfer)."""
        with self._lock:
            if self._splade_device is None:
                self._splade_device = SpladeDeviceCache(
                    self.splade, max_df=self.params.splade_max_df,
                    device=self.device)
            return self._splade_device

    def _splade_impl(self, backend: str) -> str:
        """Kernel ``impl`` for a device stage-1 backend: ``jax`` is the
        segment-sum reference on any platform, ``pallas`` the Mosaic
        kernel, which exists only on a TPU."""
        if backend == "jax":
            return "ref"
        platform = jax.default_backend()
        if platform != "tpu":
            raise RuntimeError(
                f"splade backend 'pallas' runs the Mosaic kernel and "
                f"needs a TPU, but JAX's backend here is {platform!r}; "
                f"use 'jax' or 'host' on this host")
        return "pallas"

    def reset_stage_stats(self):
        """Clear the per-stage instrumentation (in place: executors and
        benchmarks keep a stable reference to ``pipeline_stats``)."""
        self.pipeline_stats.reset()

    @property
    def stage_stats(self) -> dict:
        """Legacy view of :attr:`pipeline_stats`: stage-1 wall time /
        dispatch count vs everything after (stages 2–4 + fusion)."""
        stages = self.pipeline_stats.snapshot()["stages"]
        s1 = stages.get("splade_stage1", {})
        return {"stage1_s": s1.get("wall_s", 0.0),
                "stage1_dispatches": s1.get("dispatches", 0),
                "stage1_queries": s1.get("queries", 0),
                "rest_s": sum(r["wall_s"] for name, r in stages.items()
                              if name != "splade_stage1")}

    # ------------------------------------------------------------------
    # coordinator cache hierarchy + index-generation invalidation
    # ------------------------------------------------------------------
    def attach_caches(self, caches):
        """Attach a :class:`~repro.serving.context.CacheHierarchy`.
        Plans close over ``self`` and read ``self._caches`` per call, so
        caches can be attached (or detached with ``None``) after plans
        are compiled."""
        self._caches = caches

    def bump_index_generation(self):
        """Advance the index generation (an index mutation — upsert,
        delete, reshard — happened) and purge every cache entry computed
        under an older generation. New cache keys embed the new
        generation, so stale entries can never be served even before the
        purge completes."""
        self.index_generation = self.index_generation + 1
        caches = self._caches
        if caches is not None:
            caches.purge_stale(self.index_generation)
        return self.index_generation

    def _plaid_salt(self) -> str:
        sp = self.searcher.params
        return f"np{sp.nprobe}|cc{sp.candidate_cap}|nd{sp.ndocs}"

    def cache_salts(self, method: str):
        """(exact_salt, stage1_salt): the retriever-config components of
        the cache keys. Everything that changes an answer for identical
        query bytes must appear here — backends, first_k, normalizer,
        PLAID knobs, and the index generation."""
        p = self.params
        gen = self.index_generation
        if method == "colbert":
            s1 = f"cand|{self._plaid_salt()}|g{gen}"
        else:
            s1 = f"sp|fk{p.first_k}|b{self.splade_backend}|g{gen}"
        exact = (f"fk{p.first_k}|n{p.normalizer}|sb{self.splade_backend}"
                 f"|rb{self.rerank_backend}|{self._plaid_salt()}|g{gen}")
        return exact, s1

    def _stage1_ctx_keys(self, cb: CandidateBatch):
        """Per-query stage-1 cache keys for a batch, or None when the
        stage-1 cache is off / the batch carries no contexts."""
        caches = self._caches
        if (caches is None or caches.stage1.capacity <= 0
                or cb.ctxs is None):
            return None
        keys = [None if c is None else c.stage1_key for c in cb.ctxs]
        if all(k is None for k in keys):
            return None
        return keys

    def _stage1_group_lookup(self, cb: CandidateBatch):
        """All-or-nothing batch lookup of merged stage-1 rows (the
        sharded plans' granularity: a partial hit recomputes the whole
        batch, since the per-shard fanout runs all queries together).
        Returns stacked ``(pids_b, s_scores)`` or None."""
        keys = self._stage1_ctx_keys(cb)
        if keys is None:
            return None
        rows = [None if k is None else self._caches.stage1.get(k)
                for k in keys]
        if any(r is None for r in rows):
            return None
        return (np.stack([r[0] for r in rows]),
                np.stack([r[1] for r in rows]))

    def _stage1_group_store(self, cb: CandidateBatch):
        """Store merged stage-1 rows (full ``first_k`` width) per query.
        Skipped for degraded batches — a candidate union missing a
        shard's postings must never be replayed as a full answer."""
        keys = self._stage1_ctx_keys(cb)
        if keys is None or cb.state.get("missing_shards"):
            return
        pids_b = cb.state.get("pids_b")
        s_scores = cb.state.get("s_scores")
        if pids_b is None or s_scores is None:
            return
        gen = self.index_generation
        for i, key in enumerate(keys):
            if key is not None:
                self._caches.stage1.put(
                    key, freeze(pids_b[i], s_scores[i]), gen)

    # ------------------------------------------------------------------
    def run_splade(self, term_ids, term_weights, k: Optional[int] = None,
                   backend: Optional[str] = None):
        pids, scores = self.run_splade_batch(
            [term_ids], [term_weights], k=k, backend=backend)
        return pids[0], scores[0]

    def run_splade_batch(self, term_ids, term_weights,
                         k: Optional[int] = None,
                         backend: Optional[str] = None,
                         _record: bool = True):
        """Stage 1 for a whole micro-batch in one dispatch.

        term_ids/term_weights: sequences of per-query (Qt_i,) arrays.
        backend 'host' → vectorised CSR pass (`score_batch_host`);
        'jax'/'pallas' → device-resident padded postings (segment-sum /
        block kernel) with a fused per-query top-k. ``_record=False``
        skips stats (the plan runner accounts the stage itself)."""
        backend = backend or self.splade_backend
        if backend not in SPLADE_BACKENDS:
            raise ValueError(f"splade backend {backend!r} not in "
                             f"{SPLADE_BACKENDS}")
        k = self.params.first_k if k is None else k
        t0 = time.perf_counter()
        live = self.live
        if live is not None and live.dirty:
            # live serving always scores stage 1 on the host CSR: the
            # tombstone exclusion must happen *pre-top-k* (a masked doc
            # may not displace a survivor) and the delta segment is
            # host-resident. Cache keys embed the generation, which a
            # mutation bumps, so entries never mix backends within one
            # generation.
            out = self._run_splade_live(live, term_ids, term_weights, k)
        elif backend == "host":
            out = self.splade.score_batch_host(term_ids, term_weights, k)
        else:
            cache = self.splade_device_cache()
            out = cache.score_topk(term_ids, term_weights, k,
                                   impl=self._splade_impl(backend))
        if _record:
            self.pipeline_stats.record(
                "splade_stage1", time.perf_counter() - t0,
                queries=len(term_ids))
        return out

    def _run_splade_live(self, live, term_ids, term_weights, k: int):
        """Stage 1 under a dirty live state: base CSR scoring with
        tombstoned base pids excluded pre-top-k, merged with the delta
        segment's own top-k (owner retrievers only — shard-level
        ``LiveView``s carry tombstones but no delta; delta docs merge at
        the coordinator). The merge of disjoint-partition top-k lists
        under (score desc, pid asc) equals the top-k of the union — the
        same invariant the sharded fan-out relies on — so the result is
        exactly what one index over base∪delta minus tombstones scores."""
        base = self.splade.score_batch_host(term_ids, term_weights, k,
                                            exclude=live.base_exclude)
        delta_fn = getattr(live, "splade_delta_topk", None)
        if delta_fn is None:
            return base
        d_pids, d_scores = delta_fn(term_ids, term_weights, k)
        from repro.core.sharded import merge_topk
        return merge_topk(
            np.concatenate([base[0].astype(np.int64), d_pids], axis=1),
            np.concatenate([base[1], d_scores], axis=1), k, pad_score=0.0)

    # ------------------------------------------------------------------
    def search(self, method: str, q_emb=None, term_ids=None,
               term_weights=None, alpha: Optional[float] = None,
               k: Optional[int] = None):
        """One query as a batch of one through :meth:`search_batch_ctx`.
        Returns (pids (k,), scores (k,)), -1 padded, descending."""
        wrap = (lambda x: None if x is None else [x])
        pids, scores, _ = self.search_batch_ctx(
            method, q_embs=wrap(q_emb), term_ids=wrap(term_ids),
            term_weights=wrap(term_weights), alpha=alpha, k=k)
        return pids[0], scores[0]

    # ------------------------------------------------------------------
    # stage-graph compilation (the serving pipeline's unit of execution)
    # ------------------------------------------------------------------
    def build_batch(self, method: str, q_embs=None, term_ids=None,
                    term_weights=None, alphas=None, k: Optional[int] = None,
                    n: Optional[int] = None,
                    ctxs=None, qids=None) -> CandidateBatch:
        """Package per-query inputs into the immutable carrier a
        :class:`StagePlan` consumes. ``ctxs`` (optional per-query
        :class:`~repro.serving.context.RequestContext`) rides along so
        plan stages can consult per-request cache keys; ``qids``
        (optional request ids) so each stage's profiler span names its
        requests."""
        k = self.params.k if k is None else k
        if n is None:
            n = len(q_embs) if q_embs is not None else len(term_ids)
        pick = (lambda seq: None if seq is None else tuple(seq[:n]))
        return CandidateBatch(method=method, k=k, q_embs=pick(q_embs),
                              term_ids=pick(term_ids),
                              term_weights=pick(term_weights),
                              alphas=alphas, ctxs=pick(ctxs),
                              qids=pick(qids))

    def compile_plan(self, method: str) -> StagePlan:
        """Compile one of the four systems to its typed stage graph.

        Plans are cached per (method, stage-1 backend, rerank backend);
        the stage functions close over ``self`` and read dynamic state
        (backend, device caches) at run time. The synchronous
        :meth:`search_batch` and the pipelined executor both run the
        plan returned here, so depth-1 vs depth-N results are
        method-faithful by construction.
        """
        if method not in METHODS:
            raise ValueError(method)
        key = (method, self.splade_backend, self.rerank_backend)
        with self._lock:
            # one plan object per key: the engine keys live executors on
            # plan identity, so two racing builders must not each get a
            # distinct (but equivalent) plan
            plan = self._plans.get(key)
            if plan is None:
                plan = self._plans[key] = self._build_plan(method)
            return plan

    def _build_plan(self, method: str) -> StagePlan:
        """Stage functions obey a strict resource discipline: host-kind
        stages touch ONLY numpy (mmap gathers, padding, formatting) and
        never call into jax, because a host stage that device_puts or
        blocks on a device value serialises behind the device worker's
        in-flight dispatch and the pipeline loses its overlap. All
        host↔device transfers and result syncs live inside device-kind
        stages, so they are attributed to (and overlapped by) the
        device worker."""
        p = self.params
        searcher = self.searcher
        dr = searcher.device_resident
        gather_kind = DEVICE if dr else HOST
        access = None if dr else searcher.index.store.stats

        if method == "colbert":
            def probe(cb):
                # candidate-cache probe: when EVERY query's post-approx
                # survivor set is cached, skip stages 1-3 entirely and
                # rebuild the padded state the rerank tail consumes.
                # Batch padding replicates the last real row — exactly
                # what the cold path's deterministic device stages
                # produce for pad rows — so downstream gathers see
                # byte-identical inputs.
                keys = self._stage1_ctx_keys(cb)
                if keys is not None:
                    rows = [None if k_ is None
                            else self._caches.stage1.get(k_)
                            for k_ in keys]
                    if all(r is not None for r in rows):
                        q, q_valid = pad_query_batch(cb.q_embs)
                        B, q, q_valid, final_np = _pad_batch_rows(
                            q, q_valid, np.stack([r[0] for r in rows]))
                        n_real = np.asarray([int(r[1]) for r in rows])
                        return cb.with_state(
                            B=B, q=q, q_valid=q_valid,
                            final_pids=jnp.asarray(final_np),
                            final_np=final_np, n_real=n_real,
                            stage1_cached=True)
                st = searcher.probe_batch(cb.q_embs)
                # sync candidates to host here, on the device worker —
                # the host gather must not block on device work
                st["cand_np"] = np.asarray(st["cand"])
                return cb.with_state(**st)

            def gather_codes(cb):
                if cb.state.get("stage1_cached"):
                    return cb
                s = cb.state
                n_real = (s["cand_np"][:s["B"]] >= 0).sum(axis=1)
                if dr:
                    codes, valid = searcher.gather_codes_batch(s["cand"])
                else:
                    codes, _, valid = searcher._dedup_gather(
                        s["cand_np"], codes_only=True)
                return cb.with_state(codes=codes, cvalid=valid,
                                     n_real=n_real)

            def approx(cb):
                if cb.state.get("stage1_cached"):
                    return cb
                s = cb.state
                final_pids = searcher.approx_select_batch(
                    s["scores_c"], jnp.asarray(s["codes"]),
                    jnp.asarray(s["cvalid"]), s["q_valid"], s["cand"])
                final_np = np.asarray(final_pids)
                keys = self._stage1_ctx_keys(cb)
                if keys is not None:
                    gen = self.index_generation
                    for i, key in enumerate(keys):
                        if key is not None:
                            self._caches.stage1.put(
                                key,
                                (freeze(final_np[i])[0],
                                 int(s["n_real"][i])), gen)
                return cb.with_state(final_pids=final_pids,
                                     final_np=final_np)

            def gather_residuals(cb):
                s = cb.state
                if dr:
                    f_codes, f_packed, f_valid = \
                        searcher.gather_tokens_batch(s["final_pids"])
                else:
                    f_codes, f_packed, f_valid = searcher._dedup_gather(
                        s["final_np"], codes_only=False)
                return cb.with_state(f_codes=f_codes, f_packed=f_packed,
                                     f_valid=f_valid)

            def exact(cb):
                s = cb.state
                ex = searcher.exact_score_gathered(
                    s["q"], s["q_valid"], jnp.asarray(s["f_codes"]),
                    jnp.asarray(s["f_packed"]), jnp.asarray(s["f_valid"]),
                    s["final_pids"])
                pids, scores = searcher.finalize_topk(
                    ex, s["final_pids"], s["B"], cb.k)
                return cb.with_state(out_pids=pids, out_scores=scores)

            def fuse(cb):
                s = cb.state
                aux = [{"candidates": int(x)} for x in s["n_real"]]
                return cb.evolve(pids=s["out_pids"],
                                 scores=s["out_scores"]).with_state(aux=aux)

            def exact_fused(cb):
                # fused stage-4 tail: decompress + MaxSim + top-k in ONE
                # dispatch (no materialised (B, C) score tensor on the
                # kernel path), then host-side pid mapping — replaces
                # device_score:exact (2 dispatches) + fuse_topk's
                # finalize (top_k + take_along_axis)
                s = cb.state
                top_s, top_i = searcher.fused_topk_gathered(
                    s["q"], s["q_valid"], jnp.asarray(s["f_codes"]),
                    jnp.asarray(s["f_packed"]), jnp.asarray(s["f_valid"]),
                    s["final_np"] >= 0, cb.k)
                pids, scores = searcher.finalize_topk_fused(
                    top_s, top_i, s["final_np"], s["B"], cb.k)
                aux = [{"candidates": int(x)} for x in s["n_real"]]
                return cb.evolve(pids=pids,
                                 scores=scores).with_state(aux=aux)

            head = (Stage("plaid_probe", DEVICE, probe),
                    Stage("host_gather:codes", gather_kind, gather_codes),
                    Stage("device_score:approx", DEVICE, approx),
                    Stage("host_gather:residuals", gather_kind,
                          gather_residuals))
            if self.rerank_backend == "fused":
                tail = (Stage("fused_rerank", DEVICE, exact_fused,
                              device_dispatches=1),)
            else:
                tail = (Stage("device_score:exact", DEVICE, exact,
                              device_dispatches=4),
                        Stage("fuse_topk", DEVICE, fuse,
                              device_dispatches=0))
            return StagePlan(method=method, stages=head + tail,
                             access_stats=access)

        s1_kind = HOST if self.splade_backend == "host" else DEVICE

        def splade_stage(cb):
            # stage-1 cache: per-query rows are batch-composition
            # independent (the PR 2 parity tests pin batched == single
            # per backend), so hits and misses mix freely — only the
            # missed rows are dispatched, then scattered back in place.
            keys = self._stage1_ctx_keys(cb)
            if keys is None:
                pids_b, s_scores = self.run_splade_batch(
                    list(cb.term_ids), list(cb.term_weights), p.first_k,
                    _record=False)      # both backends return host arrays
                return cb.with_state(pids_b=pids_b, s_scores=s_scores)
            rows = [None if k_ is None else self._caches.stage1.get(k_)
                    for k_ in keys]
            miss = [i for i, r in enumerate(rows) if r is None]
            if miss:
                pids_m, scores_m = self.run_splade_batch(
                    [cb.term_ids[i] for i in miss],
                    [cb.term_weights[i] for i in miss], p.first_k,
                    _record=False)
                gen = self.index_generation
                for j, i in enumerate(miss):
                    rows[i] = (pids_m[j], scores_m[j])
                    if keys[i] is not None:
                        self._caches.stage1.put(
                            keys[i], freeze(pids_m[j], scores_m[j]), gen)
            pids_b = np.stack([r[0] for r in rows])
            s_scores = np.stack([r[1] for r in rows])
            return cb.with_state(pids_b=pids_b, s_scores=s_scores)

        if method == "splade":
            def fuse_splade(cb):
                s = cb.state
                return cb.evolve(pids=s["pids_b"][:, :cb.k],
                                 scores=s["s_scores"][:, :cb.k])

            stages = (Stage("splade_stage1", s1_kind, splade_stage),
                      Stage("fuse_splade", HOST, fuse_splade))
            return StagePlan(method=method, stages=stages,
                             access_stats=access)

        # rerank / hybrid: SPLADE candidates → residual gather → exact
        # MaxSim rescoring (+ α-fusion) → top-k
        def gather(cb):
            s = cb.state
            q, q_valid = pad_query_batch_host(cb.q_embs)
            B, q, q_valid, pids_p = _pad_batch_rows(
                q, q_valid, np.asarray(s["pids_b"]))
            if dr:
                codes, packed, valid = searcher.gather_tokens_batch(pids_p)
            else:
                codes, packed, valid = searcher._dedup_gather(
                    pids_p, codes_only=False)
            return cb.with_state(q=q, q_valid=q_valid, B=B, pids_p=pids_p,
                                 g_codes=codes, g_packed=packed,
                                 g_valid=valid)

        def score(cb):
            s = cb.state
            # dispatch only — the returned values are lazy device
            # arrays; the fuse stage's first host touch waits for them
            # with the GIL released, so the device executes batch N
            # while the host worker gathers batch N+1
            lazy = searcher.score_gathered_lazy(
                jnp.asarray(s["q"]), jnp.asarray(s["q_valid"]),
                jnp.asarray(s["g_codes"]), jnp.asarray(s["g_packed"]),
                jnp.asarray(s["g_valid"]), s["pids_p"])[:s["B"]]
            if method == "hybrid":
                # α-fusion is a jitted dispatch → it belongs to the
                # device stage, not the host-side fuse
                mask = s["pids_b"] >= 0
                final = hybrid_mod.hybrid_scores(
                    jnp.asarray(s["s_scores"]), lazy,
                    jnp.asarray(mask), alpha=jnp.asarray(cb.alphas),
                    normalizer=p.normalizer)
                return cb.with_state(final_dev=final)
            return cb.with_state(c_scores_dev=lazy)

        def fuse_rerank(cb):
            s = cb.state
            pids_b = s["pids_b"]
            if method == "rerank":
                c_scores = np.asarray(s["c_scores_dev"])   # device sync
                final = np.where(pids_b >= 0, c_scores, -np.inf)
            else:
                final = np.asarray(s["final_dev"])         # device sync
            order = np.argsort(-final, axis=1, kind="stable")[:, :cb.k]
            sorted_final = np.take_along_axis(final, order, axis=1)
            out_pids = np.where(
                sorted_final > -np.inf,
                np.take_along_axis(pids_b, order, axis=1), -1)
            return cb.evolve(pids=out_pids, scores=sorted_final)

        def score_fused(cb):
            # the whole stage-4 tail — exact scoring, masking, (hybrid)
            # α-fusion and top-k selection — as ONE lazy device
            # dispatch; cand_mask comes from host numpy so nothing else
            # touches the device here
            s = cb.state
            cand_mask = s["pids_p"] >= 0
            if method == "hybrid":
                top = searcher.fused_hybrid_topk_gathered(
                    jnp.asarray(s["q"]), jnp.asarray(s["q_valid"]),
                    jnp.asarray(s["g_codes"]), jnp.asarray(s["g_packed"]),
                    jnp.asarray(s["g_valid"]), cand_mask, s["s_scores"],
                    cb.alphas, cb.k, s["B"], p.normalizer)
            else:
                top = searcher.fused_topk_gathered(
                    jnp.asarray(s["q"]), jnp.asarray(s["q_valid"]),
                    jnp.asarray(s["g_codes"]), jnp.asarray(s["g_packed"]),
                    jnp.asarray(s["g_valid"]), cand_mask, cb.k)
            return cb.with_state(top_s=top[0], top_i=top[1])

        def fuse_fused(cb):
            # close the async window: sync the (already-selected) top-k
            # and map candidate-axis indices to pids — no argsort, no
            # extra dispatches. Width is min(k, first_k), exactly the
            # split tail's contract.
            s = cb.state
            top_s = np.asarray(s["top_s"])[:s["B"]]    # device sync
            top_i = np.asarray(s["top_i"])[:s["B"]]
            out_pids = np.where(
                top_s > -np.inf,
                np.take_along_axis(np.asarray(s["pids_b"]),
                                   np.clip(top_i, 0, None).astype(np.int64),
                                   axis=1), -1)
            return cb.evolve(pids=out_pids, scores=top_s)

        # score opens the async window (its dispatch returns lazy device
        # values); fuse closes it (first host touch blocks). The
        # single-worker scheduler parks a batch between the two while it
        # runs the next batch's host stages — and fuse is DEVICE-kind so
        # that in threaded mode the sync also stays off the gather
        # worker. The fused backend keeps the identical two-stage
        # async shape (so pipeline overlap is preserved) but its dispatch
        # stage launches ONE device computation instead of 3-4 and its
        # sync stage launches none.
        if self.rerank_backend == "fused":
            tail = (Stage("fused_rerank", DEVICE, score_fused,
                          opens_async=True, device_dispatches=1),
                    Stage("fused_rerank:sync", DEVICE, fuse_fused,
                          closes_async=True, device_dispatches=0))
        else:
            tail = (Stage("device_score:maxsim", DEVICE, score,
                          opens_async=True,
                          device_dispatches=4 if method == "hybrid" else 3),
                    Stage("fuse_topk", DEVICE, fuse_rerank,
                          closes_async=True, device_dispatches=0))
        stages = (Stage("splade_stage1", s1_kind, splade_stage),
                  Stage("host_gather:residuals", gather_kind,
                        gather)) + tail
        return StagePlan(method=method, stages=stages, access_stats=access)

    # ------------------------------------------------------------------
    def search_batch(self, method, q_embs=None, term_ids=None,
                     term_weights=None, alpha=None, k: Optional[int] = None,
                     ctxs=None):
        """Cross-query batched retrieval over any of the four methods.

        ``method``: one method name for the whole batch, or a sequence of
        per-query names (mixed batches are grouped and each group runs
        batched). ``q_embs``/``term_ids``/``term_weights``: per-query
        sequences (ragged lengths fine). ``alpha``: scalar, per-query
        sequence, or None (per-params default). Returns
        (pids (B, k), scores (B, k)) matching per-query :meth:`search`.

        Legacy wrapper over :meth:`search_batch_ctx`: the typed outcome
        is folded back into the thread-local degraded note for callers
        that still read ``last_missing_shards``.
        """
        pids, scores, outcome = self.search_batch_ctx(
            method, q_embs=q_embs, term_ids=term_ids,
            term_weights=term_weights, alpha=alpha, k=k, ctxs=ctxs)
        self._degraded_tls.missing = outcome.missing_shards
        return pids, scores

    def search_batch_ctx(self, method, q_embs=None, term_ids=None,
                         term_weights=None, alpha=None,
                         k: Optional[int] = None, ctxs=None):
        """:meth:`search_batch` with a typed outcome: returns
        ``(pids, scores, BatchOutcome)``. The outcome carries what the
        thread-local side channel used to (missing shards under degraded
        shard groups), returned to the caller instead of stashed.

        ``ctxs``: optional per-query
        :class:`~repro.serving.context.RequestContext` sequence — when a
        cache hierarchy is attached, plan stages consult each context's
        ``stage1_key`` for the candidate-gather cache.

        Runs the method's compiled :class:`StagePlan` synchronously —
        the ``pipeline_depth=1`` path of the stage-graph executor. A
        call whose outcome names missing shards counts once in the
        ``degraded_batches`` counter, however many method groups it
        held.

        With a live index attached the whole batch holds the compaction
        gate's read side: queries proceed concurrently and only the
        atomic generation swap excludes them.
        """
        gate = getattr(self.live, "gate", None)
        with gate.read() if gate is not None else contextlib.nullcontext():
            out = self._search_batch_ctx_impl(method, q_embs, term_ids,
                                              term_weights, alpha, k, ctxs)
        if out[2].missing_shards:
            self.pipeline_stats.counter("degraded_batches")
        return out

    def _search_batch_ctx_impl(self, method, q_embs, term_ids,
                               term_weights, alpha, k, ctxs):
        p = self.params
        k = p.k if k is None else k
        n = len(q_embs) if q_embs is not None else len(term_ids)

        if not isinstance(method, str):
            methods = list(method)
            if len(set(methods)) > 1:
                return self._search_batch_mixed(methods, q_embs, term_ids,
                                                term_weights, alpha, k,
                                                ctxs)
            method = methods[0]

        alphas = self._alpha_array(alpha, n)
        live = self.live
        if live is not None and live.dirty and self._live_inline:
            qids = (None if ctxs is None or any(c is None for c in ctxs)
                    else [c.qid for c in ctxs])
            return self._search_batch_live(live, method, q_embs, term_ids,
                                           term_weights, alphas, k, qids)
        cb = self.build_batch(method, q_embs, term_ids, term_weights,
                              alphas, k, n, ctxs=ctxs)
        cb = self.compile_plan(method).run(cb, stats=self.pipeline_stats)
        return cb.pids, cb.scores, BatchOutcome(
            missing_shards=tuple(cb.state.get("missing_shards", ())))

    # ------------------------------------------------------------------
    # live (mutable) index: overlay serving, mutations, compaction
    # ------------------------------------------------------------------
    # Unsharded retrievers serve a dirty live state through the inline
    # overlay path below; sharded groups instead inject the live state
    # into their merge/fuse bodies (set False there) so per-shard plans
    # stay frozen.
    _live_inline = True

    def enable_live(self):
        """Attach a :class:`~repro.index.live.LiveIndexState` and return
        it. Idempotent. Until the first mutation the state is clean and
        every serve path is byte-for-byte the frozen one."""
        if self.live is not None:
            return self.live
        if self.searcher.device_resident:
            raise ValueError("live index requires the host (mmap) tier; "
                             "device_resident pools are frozen")
        from repro.index.live import LiveIndexState
        live = self.live = LiveIndexState(self.searcher.index, self.splade)

        def gauges():
            st = live.stats()
            return {"delta_docs": st["delta_docs"],
                    "tombstones": st["tombstones"]}
        self.pipeline_stats.gauge(gauges)
        return live

    def _require_live(self):
        if self.live is None:
            raise RuntimeError("live index not enabled (enable_live / "
                               "--live)")
        return self.live

    @contextlib.contextmanager
    def _live_span(self, name: str, queries: int = 0, **stats):
        """Profiler span ``name`` (``stage:live_*`` or ``live:*``, with
        ``stats``) whose interval, once the body has returned, is also
        recorded in :attr:`pipeline_stats` as a plan stage's is, under
        the name less its ``stage:`` prefix with ``:`` made ``_``
        (``stage:live_delta`` → ``live_delta``, ``live:upsert`` →
        ``live_upsert``)."""
        with TraceAnnotation(name, **stats):
            t0 = time.perf_counter()
            yield
            self.pipeline_stats.record(
                name.removeprefix("stage:").replace(":", "_"),
                time.perf_counter() - t0, queries=queries)

    def live_upsert(self, doc_emb, term_ids, term_weights,
                    doc_len=None) -> int:
        """Append a document to the delta segment → its global pid.
        Bumps the index generation so result/stage-1 caches invalidate."""
        live = self._require_live()
        n = len(doc_emb) if doc_len is None else int(doc_len)
        with self._live_span("live:upsert", doc_len=n):
            pid = live.upsert(doc_emb, term_ids, term_weights, doc_len)
        self.pipeline_stats.counter("live_upserts")
        self.bump_index_generation()
        return pid

    def live_delete(self, gpid: int) -> bool:
        """Tombstone a global pid; True if it was live before."""
        live = self._require_live()
        with self._live_span("live:delete"):
            ok = live.delete(gpid)
        if ok:
            self.pipeline_stats.counter("live_deletes")
            self.bump_index_generation()
        return ok

    def live_stats(self) -> dict:
        live = self.live
        if live is None:
            return {}
        out = live.stats()
        out["generation"] = self.index_generation
        return out

    def compact_live(self):
        """Merge the delta prefix into a new on-disk index generation
        and atomically swap the serve handles.

        The build runs entirely off-gate (queries keep flowing against
        base+delta); only the final handle swap takes the write gate,
        drains in-flight readers, and bumps the generation. Global pids
        are stable across the swap — delta doc ``j`` simply becomes base
        doc ``base_n + j`` — so tombstones and cached client-side pids
        stay valid."""
        live = self._require_live()
        n_take = live.snapshot_delta()
        if n_take == 0:
            return None
        from repro.index import live as live_mod
        from repro.index.builder import ColBERTIndex
        idx = self.searcher.index
        gen = self.index_generation + 1
        col_dir = idx.path.with_name(f"{idx.path.name}.g{gen}")
        spl_dir = idx.path.with_name(f"splade.g{gen}")
        with self._live_span("live:compact", n_take=n_take):
            live_mod.compact_colbert_dir(idx, live, n_take, col_dir)
            live_mod.compact_splade_dir(self.splade, live, n_take, spl_dir)
            new_index = ColBERTIndex(col_dir, mode=idx.store.mode)
            new_searcher = PLAIDSearcher(new_index, self.searcher.params,
                                         device_resident=False,
                                         device=self.searcher.device)
            new_splade = SpladeIndex.load(spl_dir)
            with live.gate.write():
                self.splade = new_splade
                self.searcher = new_searcher
                with self._lock:
                    self._plans.clear()
                    self._splade_device = None
                live.rebase(n_take)
                self.bump_index_generation()
        self.pipeline_stats.counter("live_compactions")
        return {"compacted": n_take, "colbert_dir": str(col_dir),
                "splade_dir": str(spl_dir)}

    def _live_exact(self, live, q, q_valid, pids_p: np.ndarray, b: int,
                    qids=None):
        """Exact scores (host (Bp, C) f32) for a pid matrix that may mix
        base and delta pids; its first ``b`` rows are the real queries.
        Each origin is scored by its own gather + decompress-MaxSim
        dispatch and scattered positionally — per-candidate scores are
        independent, so the stitched matrix is bitwise what one dispatch
        over a unified index would produce. Each origin's span carries
        its dispatch's shapes (``B``, ``C``, ``Ld``, ``pd``, ``Lq``,
        ``dim``, ``K``, ``nbits``); ``delta_rows_scored`` counts the
        real queries' delta candidates."""
        pids_p = np.asarray(pids_p)
        delta_mask = pids_p >= live.base_n
        shapes = {"B": pids_p.shape[0], "C": pids_p.shape[1],
                  "Ld": live.doc_maxlen, "pd": live.packed_dim,
                  "Lq": q.shape[1], "dim": q.shape[2],
                  "K": live.n_centroids, "nbits": live.nbits,
                  **span_ids(qids)}
        with self._live_span("stage:live_base_exact", b, **shapes):
            base_pids = np.where(delta_mask, -1, pids_p)
            codes, packed, valid = self.searcher._dedup_gather(
                base_pids, codes_only=False)
            base_scores = np.asarray(self.searcher.score_gathered_lazy(
                jnp.asarray(q), jnp.asarray(q_valid), jnp.asarray(codes),
                jnp.asarray(packed), jnp.asarray(valid), base_pids))
        if delta_mask.any():
            self.pipeline_stats.counter("delta_rows_scored",
                                        int(delta_mask[:b].sum()))
            with self._live_span("stage:live_delta", b, **shapes):
                d_scores = live.exact_scores(
                    q, q_valid, np.where(delta_mask, pids_p, -1))
            return np.where(delta_mask, d_scores,
                            base_scores).astype(np.float32)
        return base_scores.astype(np.float32)

    def _search_batch_live(self, live, method, q_embs, term_ids,
                           term_weights, alphas, k: int, qids=None):
        """Overlay serving for a dirty live state (one
        ``stage:live_overlay`` span, naming the batch's ``qids``):
        compose the same stage primitives the frozen plans run — base
        index scoring plus the delta segment, tombstones filtered at
        every merge — without touching the compiled plans (which stay
        bitwise-frozen for the inert case). Always the split stage-4
        tail, bitwise-identical to the fused one (the fused/split
        parity tests hold them to it)."""
        n = len(q_embs) if q_embs is not None else len(term_ids)
        with self._live_span("stage:live_overlay", n, **span_ids(qids)):
            return self._search_batch_live_impl(
                live, method, q_embs, term_ids, term_weights, alphas, k,
                qids)

    def _search_batch_live_impl(self, live, method, q_embs, term_ids,
                                term_weights, alphas, k: int, qids):
        from repro.core import plaid as plaid_mod
        from repro.core.sharded import merge_topk
        p = self.params
        searcher = self.searcher
        outcome = BatchOutcome()

        if method in ("splade", "rerank", "hybrid"):
            with self._live_span("stage:live_stage1", len(term_ids),
                                 **span_ids(qids)):
                pids_b, s_scores = self.run_splade_batch(
                    list(term_ids), list(term_weights), p.first_k)
            if method == "splade":
                return pids_b[:, :k], s_scores[:, :k], outcome
            q, q_valid = pad_query_batch_host(q_embs)
            B, q, q_valid, pids_p = _pad_batch_rows(
                q, q_valid, np.asarray(pids_b))
            c_scores = self._live_exact(live, q, q_valid, pids_p, B,
                                        qids)[:B]
            if method == "rerank":
                final = np.where(pids_b >= 0, c_scores, -np.inf)
            else:
                mask = pids_b >= 0
                final = np.asarray(hybrid_mod.hybrid_scores(
                    jnp.asarray(s_scores), jnp.asarray(c_scores),
                    jnp.asarray(mask), alpha=jnp.asarray(alphas),
                    normalizer=p.normalizer))
            order = np.argsort(-final, axis=1, kind="stable")[:, :k]
            sorted_final = np.take_along_axis(final, order, axis=1)
            out_pids = np.where(sorted_final > -np.inf,
                                np.take_along_axis(pids_b, order, axis=1),
                                -1)
            return out_pids, sorted_final, outcome

        if method != "colbert":
            raise ValueError(method)
        sp = searcher.params
        # stages 1-2 on the frozen base, mirroring probe_batch (exposed
        # here because the overlay needs the probed cids for the delta
        # IVF, which probe_batch does not return)
        q, q_valid = plaid_mod.pad_query_batch(q_embs)
        B, q, q_valid = _pad_batch_rows(q, q_valid)
        scores_c, cids = plaid_mod.stage1_centroid_probe_batch(
            q, q_valid, searcher.centroids, sp.nprobe)
        cand = plaid_mod.stage2_candidates_batch(
            searcher.ivf_padded, cids, sp.candidate_cap)
        cand_np = np.asarray(cand)
        n_real = (cand_np[:B] >= 0).sum(axis=1)

        codes, _, valid = searcher._dedup_gather(cand_np, codes_only=True)
        approx = plaid_mod.stage3_approx_score_batch(
            scores_c, jnp.asarray(codes), jnp.asarray(valid), q_valid)
        approx_np = np.asarray(jnp.where(cand >= 0, approx, -jnp.inf))

        # tombstoned base candidates drop out pre-merge (pid -1 / -inf,
        # exactly how padded candidate slots already behave)
        tomb = live.is_tombstoned(np.clip(cand_np, 0, None)) & (cand_np >= 0)
        base_cand = np.where(tomb, -1, cand_np).astype(np.int64)
        approx_np = np.where(tomb, -np.inf, approx_np).astype(np.float32)

        # delta candidates from the probed centroids' delta postings
        d_lists = live.delta_candidates(np.asarray(cids))
        W = max(1, max((len(x) for x in d_lists), default=0))
        d_mat = np.full((cand_np.shape[0], W), -1, np.int64)
        for b, arr in enumerate(d_lists):
            d_mat[b, :len(arr)] = arr
        d_approx = live.approx_scores(scores_c, q_valid, d_mat)

        ndocs = min(sp.ndocs, sp.candidate_cap)
        final_np, _ = merge_topk(
            np.concatenate([base_cand, d_mat], axis=1),
            np.concatenate([approx_np, d_approx], axis=1), ndocs)

        exact = self._live_exact(live, q, q_valid, final_np, B, qids)
        out_pids, out_scores = searcher.finalize_topk(
            jnp.asarray(exact), jnp.asarray(final_np), B, k)
        return out_pids, out_scores, outcome

    # ------------------------------------------------------------------
    # degraded-answer note of :meth:`search_batch` (sharded process
    # groups only; the in-process backends never miss a shard)
    # ------------------------------------------------------------------
    @property
    def _degraded_tls(self):
        # lazy: the sharded subclasses build themselves without calling
        # this __init__
        return self.__dict__.setdefault("_degraded_tls_obj",
                                        threading.local())

    def last_missing_shards(self) -> tuple:
        """Missing-shard ids of this thread's last ``search_batch``
        (empty when it was a full answer); reading clears the note."""
        tls = self._degraded_tls
        out = getattr(tls, "missing", ())
        tls.missing = ()
        return out

    def _alpha_array(self, alpha, n: int) -> np.ndarray:
        if alpha is None:
            return np.full(n, self.params.alpha, np.float32)
        if np.ndim(alpha) == 0:
            return np.full(n, float(alpha), np.float32)
        return np.asarray([self.params.alpha if a is None else float(a)
                           for a in alpha], np.float32)

    @staticmethod
    def scatter_group(out_pids, out_scores, idx, pids, scores):
        """Scatter one method group's results back into request order.
        splade-first groups return min(k, first_k) columns — they fill
        the prefix, leaving the (-1, -inf) tail as padding. Shared with
        the pipelined engine so mixed-batch semantics cannot drift."""
        w = pids.shape[1]
        out_pids[idx, :w] = pids
        out_scores[idx, :w] = scores

    def _search_batch_mixed(self, methods, q_embs, term_ids, term_weights,
                            alpha, k: int, ctxs=None):
        """Group a mixed-method batch by method, run each group batched,
        and scatter results back into request order. Group outcomes are
        merged (missing-shard union across groups)."""
        n = len(methods)
        alphas = self._alpha_array(alpha, n)
        out_pids = np.full((n, k), -1, np.int64)
        out_scores = np.full((n, k), -np.inf, np.float32)
        outcome = BatchOutcome()
        for m in dict.fromkeys(methods):
            idx = [i for i, mi in enumerate(methods) if mi == m]
            pick = (lambda seq: None if seq is None
                    else [seq[i] for i in idx])
            pids, scores, out = self._search_batch_ctx_impl(
                m, pick(q_embs), pick(term_ids), pick(term_weights),
                alphas[idx], k, pick(ctxs))
            outcome = outcome.merge(out)
            self.scatter_group(out_pids, out_scores, idx, pids, scores)
        return out_pids, out_scores, outcome
