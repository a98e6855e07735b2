"""Scatter-gather serving over a sharded SPLADE/PLAID/mmap index.

The corpus is partitioned into ``n_shards`` contiguous document ranges
(``repro.index.sharding``); each shard owns its own SPLADE postings
slice, PLAID IVF slice, and mmap ``PagedStore`` segment, wrapped in an
ordinary per-shard :class:`MultiStageRetriever`. This module's
:class:`ShardedRetriever` presents the same retriever interface over
the whole group by compiling *sharded* stage plans:

* per-shard host work runs as pooled ``fanout`` stages
  (``Stage.fanout``) — the stage function executes once per shard,
  concurrently on the group's thread pool. For ``host_gather`` stages
  that is the point of the topology: independent mmap segments fault
  independent page streams, so gather bandwidth scales with the shard
  count instead of serialising on one file's page-in queue. Device
  work either fans out with async dispatches (PLAID stages) or runs as
  a dispatch-all-then-sync-all group stage (SPLADE stage 1), so shard
  devices execute concurrently without pooling the GIL-bound Python
  dispatch itself.
* shard-local candidates are remapped to **global** doc ids
  (``local + shard_offset``) the moment they leave a shard, and a
  ``merge_topk`` fuse stage combines per-shard top-k lists into the
  global ranking.

Two worker backends share this plan vocabulary (and the merge/fuse
stage bodies, so they cannot drift):

* :class:`ShardedRetriever` — **thread workers**: every shard lives in
  this process; per-shard host gathers fan out on a thread pool,
  device dispatches are async.
* :class:`ProcessShardGroup` — **process workers**: each shard is its
  own OS process (``repro.serving.worker``) owning its mmap segment,
  page-cache working set, SPLADE device cache, and GIL; per-shard
  stage work crosses a compact RPC (``repro.serving.rpc``) and comes
  back as synced numpy. Selected by ``--shard-workers=process`` on
  ``repro.launch.serve``.

Parity contract (tested in ``tests/test_sharding.py`` and
``tests/test_process_group.py``): shard-local scores are bit-identical
to the single index's scores for the same document (shared
quantisation / geometry), and every top-k selection — per shard and at
the merges — orders by (score desc, pid asc). Top-k selection
distributes over a partition under that total order, so shards=k
returns the same results as shards=1 for all four methods, under
either worker backend. Two documented deviations: a per-shard
``candidate_cap`` truncates later than a global one (strictly more
candidates survive — never fewer), and exact-score ties at the final
merge resolve by global pid rather than approx-rank.
"""

from __future__ import annotations

import os
import pathlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.common.utils import next_pow2 as _next_pow2
from repro.core import hybrid as hybrid_mod
from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import (
    _pad_batch_rows,
    pad_query_batch_host,
    stage3_approx_score_batch,
)
from repro.serving.pipeline import (
    DEVICE,
    HOST,
    PipelineStats,
    Stage,
    StagePlan,
)


def merge_topk(pids: np.ndarray, scores: np.ndarray, k: int,
               pad_score: float = -np.inf):
    """Merge concatenated per-shard top-k lists into the global top-k.

    ``pids``/``scores``: (B, S·K) with -1 marking padding. Selection
    orders by (score desc, global pid asc) — the same total order every
    per-shard list was built with, so the merged prefix equals the
    single-index top-k even through score ties. Returns
    ((B, k) pids -1-padded, (B, k) scores ``pad_score``-padded)."""
    key = np.where(pids >= 0, scores, -np.inf).astype(np.float32)
    # lexsort: last key is primary → score desc, then pid asc; padding
    # (-inf) sorts to the back regardless of its pid
    order = np.lexsort((np.where(pids >= 0, pids, np.iinfo(np.int64).max),
                        -key.astype(np.float64)), axis=1)[:, :k]
    top = np.take_along_axis(key, order, axis=1)
    out_pids = np.where(top > -np.inf,
                        np.take_along_axis(pids, order, axis=1), -1)
    out_scores = np.where(top > -np.inf, top, pad_score).astype(np.float32)
    w = order.shape[1]
    if w < k:
        out_pids = np.pad(out_pids, ((0, 0), (0, k - w)),
                          constant_values=-1)
        out_scores = np.pad(out_scores.astype(np.float32),
                            ((0, 0), (0, k - w)),
                            constant_values=np.float32(pad_score))
    return out_pids.astype(np.int64), out_scores


def compact_owned(gpids: np.ndarray, lo: int, hi: int, min_w: int = 8):
    """Compact one shard's slice of a global candidate matrix.

    ``gpids``: (B, C) global pids (−1 pad). Returns (cols, local), both
    (B, W) with W = pow2 bucket of the densest row's owned count (≤ C):
    ``local`` holds shard-local pids for the candidates this shard owns
    (−1 pad) and ``cols`` the *global column* each came from, so scores
    computed on the narrow slice scatter back into the global matrix
    (:func:`scatter_scores`). Gather/score work per shard is then
    O(owned) ≈ C/S instead of O(C) — without this, every shard pays the
    full candidate width and scatter-gather costs S× the single index.
    """
    owned = (gpids >= lo) & (gpids < hi)
    w = int(owned.sum(axis=1).max()) if gpids.size else 0
    W = min(_next_pow2(max(w, min_w)), max(gpids.shape[1], 1))
    # stable sort on ~owned floats owned columns to the front, keeping
    # their global order
    order = np.argsort(~owned, axis=1, kind="stable")[:, :W]
    ow = np.take_along_axis(owned, order, axis=1)
    cols = np.where(ow, order, -1)
    local = np.where(ow, np.take_along_axis(gpids, order, axis=1) - lo, -1)
    return cols, local


def scatter_scores(out: np.ndarray, cols: np.ndarray,
                   scores: np.ndarray):
    """Scatter one shard's (B, W) scores back into the (B, C) global
    matrix at the columns ``compact_owned`` recorded (−1 skipped)."""
    m = cols >= 0
    rows = np.broadcast_to(np.arange(out.shape[0])[:, None],
                           cols.shape)[m]
    out[rows, cols[m]] = scores[m]


# ---------------------------------------------------------------------------
# shared merge/fuse stage bodies
#
# Both shard-group backends — in-process thread workers
# (:class:`ShardedRetriever`) and shared-nothing process workers
# (:class:`ProcessShardGroup`) — run these exact functions for every
# coordinator-side merge and fuse, so the two backends cannot drift:
# given byte-identical per-shard states, the merged ranking is
# byte-identical by construction.
#
# Degraded mode: under ``allow_degraded`` a shard whose every replica
# is down contributes a ``{"missing": True}`` state *in its slot* (the
# shard axis stays positional — downstream offsets indexing depends on
# it). The merges skip missing slots and record the missing shard ids
# in ``cb.state["missing_shards"]``, so a partial answer is explicit
# all the way to the server response. A batch with zero surviving
# shards still fails (there is nothing to merge).
# ---------------------------------------------------------------------------

def _live_shard_states(shard_states):
    """Split the shard axis into surviving states (with their shard
    index) and the missing shard ids; raises when nothing survived."""
    live = [(i, s) for i, s in enumerate(shard_states)
            if not s.get("missing")]
    missing = tuple(i for i, s in enumerate(shard_states)
                    if s.get("missing"))
    if not live:
        from repro.serving.transport import ShardUnavailable
        raise ShardUnavailable(
            "every shard of the batch is unavailable — no partial "
            "answer to degrade to")
    return live, missing


def _note_missing(cb, missing):
    """Record (union) missing shard ids on the batch state; a no-op on
    the healthy path so thread-backend state stays byte-identical."""
    if not missing:
        return cb
    prior = cb.state.get("missing_shards", ())
    return cb.with_state(
        missing_shards=tuple(sorted(set(prior) | set(missing))))


def _concat_shard_topk(shard_states):
    """Concatenate per-shard stage-1 results (already remapped to
    global pids) along the candidate axis, skipping missing shards."""
    live, missing = _live_shard_states(shard_states)
    pids = np.concatenate([s["pids"] for _, s in live], axis=1)
    scores = np.concatenate([s["scores"] for _, s in live], axis=1)
    return pids, scores, missing


def _append_splade_delta(cb, pids, scores, first_k: int, live):
    """Widen the concatenated per-shard stage-1 rows with the live
    delta segment's top-k (global pids ≥ ``live.base_n``). Tombstoned
    *base* docs never reach here — each shard excluded them pre-top-k —
    and tombstoned delta docs are excluded inside ``splade_delta_topk``,
    so the merge below sees only surviving documents."""
    if live is None or not live.n_delta:
        return pids, scores
    d_pids, d_scores = live.splade_delta_topk(
        list(cb.term_ids), list(cb.term_weights), first_k)
    return (np.concatenate([pids, d_pids], axis=1),
            np.concatenate([scores, d_scores], axis=1))


def fuse_splade_state(cb, first_k: int, live=None):
    """Terminal fuse for the splade-only method: merge the per-shard
    stage-1 lists and truncate to the request's k. The full
    ``first_k``-wide merged rows are stashed in state so the stage-1
    cache can store them (a splade answer warms the same entry a later
    rerank/hybrid request reuses)."""
    pids, scores, missing = _concat_shard_topk(cb.shard_states)
    pids, scores = _append_splade_delta(cb, pids, scores, first_k, live)
    pids_b, s_scores = merge_topk(pids, scores, first_k, pad_score=0.0)
    cb = cb.evolve(pids=pids_b[:, :cb.k], scores=s_scores[:, :cb.k])
    cb = cb.with_state(pids_b=pids_b, s_scores=s_scores)
    return _note_missing(cb, missing)


def stage1_state_from_rows(cb, pids_b, s_scores):
    """Rebuild :func:`merge_stage1_state`'s output from cached merged
    rows — the stage-1 cache-hit path. The padding ops are the same
    calls the cold merge makes, so downstream gathers see byte-identical
    inputs."""
    B, q, q_valid, gp = _pad_batch_rows(
        *pad_query_batch_host(cb.q_embs), pids_b)
    return cb.with_state(pids_b=pids_b, s_scores=s_scores,
                         q=q, q_valid=q_valid, B=B, gp=gp)


def merge_stage1_state(cb, first_k: int, live=None):
    """(B, first_k) global candidates — identical content and order to
    the single index's ``run_splade_batch`` — plus the padded query
    batch the downstream gather/score stages consume."""
    pids, scores, missing = _concat_shard_topk(cb.shard_states)
    pids, scores = _append_splade_delta(cb, pids, scores, first_k, live)
    pids_b, s_scores = merge_topk(pids, scores, first_k, pad_score=0.0)
    q, q_valid = pad_query_batch_host(cb.q_embs)
    B, q, q_valid, gp = _pad_batch_rows(q, q_valid, pids_b)
    return _note_missing(
        cb.with_state(pids_b=pids_b, s_scores=s_scores,
                      q=q, q_valid=q_valid, B=B, gp=gp), missing)


def fuse_scatter_rerank(cb, method: str, normalizer: str, live=None):
    """Terminal rerank/hybrid fuse: sync each shard's narrow score
    slice (``c_dev`` — lazy device value or already-synced numpy),
    scatter it back into the global candidate columns, α-fuse for
    hybrid, and take the stable (score desc, pid asc) top-k."""
    st = cb.state
    pids_b = st["pids_b"]
    c_scores = np.full(pids_b.shape, -np.inf, np.float32)
    missing = []
    for i, s in enumerate(cb.shard_states):
        if s.get("missing"):
            missing.append(i)
            continue
        scatter_scores(c_scores, s["cols"][:pids_b.shape[0]],
                       np.asarray(s["c_dev"]))
    if live is not None and live.n_delta:
        # delta candidates are owned by no shard (their pids lie past
        # every boundary) — score them at the coordinator with the same
        # decompress+MaxSim kernel and fill their columns
        dmask = pids_b >= live.base_n
        if dmask.any():
            d_pids = np.where(dmask, pids_b, -1)
            pad = st["q"].shape[0] - d_pids.shape[0]
            if pad:
                d_pids = np.pad(d_pids, ((0, pad), (0, 0)),
                                constant_values=-1)
            d_scores = live.exact_scores(st["q"], st["q_valid"], d_pids)
            c_scores = np.where(dmask, d_scores[:pids_b.shape[0]],
                                c_scores)
    if method == "rerank":
        final = np.where(pids_b >= 0, c_scores, -np.inf)
    else:
        # candidates owned by a missing shard never received an exact
        # score: keep them out of the hybrid normalization. On the
        # healthy path every valid candidate has a finite score, so
        # this mask equals the plain ``pids_b >= 0`` mask bit-for-bit.
        mask = (pids_b >= 0) & (c_scores > -np.inf)
        final = np.asarray(hybrid_mod.hybrid_scores(
            jnp.asarray(st["s_scores"]), jnp.asarray(c_scores),
            jnp.asarray(mask), alpha=jnp.asarray(cb.alphas),
            normalizer=normalizer))
        if missing:
            final = np.where(mask, final, -np.inf)
    order = np.argsort(-final, axis=1, kind="stable")[:, :cb.k]
    sorted_final = np.take_along_axis(final, order, axis=1)
    out_pids = np.where(
        sorted_final > -np.inf,
        np.take_along_axis(pids_b, order, axis=1), -1)
    return _note_missing(cb.evolve(pids=out_pids, scores=sorted_final),
                         missing)


def merge_approx_state(cb, offsets, ndocs: int, live=None):
    """Global PLAID survivor selection: remap per-shard candidates to
    global pids, merge raw approx scores, and apply the ndocs cut
    *globally* (a shard-local cut would diverge from the single-index
    path). With a live overlay, tombstoned base candidates drop out
    pre-merge (pid −1 / −inf, exactly how padded candidate slots
    already behave) and the delta segment contributes its own
    candidates, approx-scored at the coordinator from the same probed
    centroid scores the shards used."""
    alive, missing = _live_shard_states(cb.shard_states)
    gpids = np.concatenate(
        [np.where(s["cand_np"] >= 0, s["cand_np"] + offsets[i], -1)
         for i, s in alive], axis=1)
    ascore = np.concatenate([s["approx_np"] for _, s in alive], axis=1)
    if live is not None and live.dirty:
        tomb = live.tombstone_array()
        if tomb.size:
            drop = np.isin(gpids, tomb) & (gpids >= 0)
            ascore = np.where(drop, -np.inf, ascore).astype(np.float32)
            gpids = np.where(drop, -1, gpids)
        if live.n_delta:
            d_lists = live.delta_candidates(np.asarray(cb.state["cids"]))
            W = max(1, max((len(x) for x in d_lists), default=0))
            d_mat = np.full((gpids.shape[0], W), -1, np.int64)
            for b, arr in enumerate(d_lists):
                d_mat[b, :len(arr)] = arr
            d_approx = live.approx_scores(
                cb.state["scores_c"], cb.state["q_valid"], d_mat)
            gpids = np.concatenate([gpids, d_mat], axis=1)
            ascore = np.concatenate([ascore, d_approx], axis=1)
    final_g, _ = merge_topk(gpids, ascore, ndocs)
    n_real = sum(s["n_real"][:cb.state["B"]] for _, s in alive)
    return _note_missing(cb.with_state(final_g=final_g, n_real=n_real),
                         missing)


def fuse_colbert_state(cb, live=None):
    """Terminal PLAID fuse: every global candidate is owned by exactly
    one shard — scatter each shard's narrow exact-score slice back into
    the global matrix and merge. Delta candidates (owned by no shard)
    are exact-scored at the coordinator."""
    st = cb.state
    B, g = st["B"], st["final_g"]
    ex = np.full(g.shape, -np.inf, np.float32)
    missing = []
    for i, s in enumerate(cb.shard_states):
        if s.get("missing"):
            missing.append(i)
            continue
        scatter_scores(ex, s["cols"], s["exact_np"])
    if live is not None and live.n_delta:
        dmask = g >= live.base_n
        if dmask.any():
            d_pids = np.where(dmask, g, -1)
            d_scores = live.exact_scores(st["q"], st["q_valid"], d_pids)
            ex = np.where(dmask, d_scores, ex)
    out_pids, out_scores = merge_topk(g[:B], ex[:B], cb.k)
    aux = [{"candidates": int(x)} for x in st["n_real"]]
    return _note_missing(
        cb.evolve(pids=out_pids, scores=out_scores).with_state(aux=aux),
        missing)


class CombinedAccessStats:
    """Duck-typed ``AccessStats`` view over a shard group: ``snapshot``
    sums the per-segment counters so sharded plans report pages/tokens
    exactly like a single store would."""

    def __init__(self, parts: Sequence):
        self.parts = list(parts)

    def snapshot(self) -> dict:
        out: dict = {}
        for part in self.parts:
            for key, val in part.snapshot().items():
                out[key] = out.get(key, 0) + val
        return out

    def reset(self):
        for part in self.parts:
            part.reset()


class ShardedRetriever(MultiStageRetriever):
    """Scatter-gather retriever over per-shard ``MultiStageRetriever``s.

    ``shards``: one retriever per contiguous doc range;
    ``shard_offsets``: (n_shards+1,) global doc-id boundaries (shard i
    owns global pids [offsets[i], offsets[i+1])). All shards must share
    params (the plan closes over one copy).

    With ``n_shards == 1`` every entry point delegates to the single
    shard, so the one-shard group is *bitwise* the unsharded path.
    """

    def __init__(self, shards: Sequence[MultiStageRetriever],
                 shard_offsets, pool: Optional[ThreadPoolExecutor] = None):
        if not shards:
            raise ValueError("empty shard group")
        self.shards = list(shards)
        self.offsets = np.asarray(shard_offsets, np.int64)
        if len(self.offsets) != len(self.shards) + 1:
            raise ValueError(
                f"{len(self.shards)} shards need {len(self.shards) + 1} "
                f"boundaries, got {len(self.offsets)}")
        for sh in self.shards[1:]:
            if sh.params != self.shards[0].params:
                raise ValueError("shards must share MultiStageParams")
        self.params = self.shards[0].params
        self.n_shards = len(self.shards)
        self.n_docs = int(self.offsets[-1])
        self._lock = threading.Lock()
        self._live_mut = threading.Lock()
        self._plans: dict = {}
        self.pipeline_stats = PipelineStats()
        # gather concurrency capped at the core count: more threads than
        # cores just thrash the GIL between the gathers' Python segments
        # (measured 2x slower at 4 shards on 2 cores) without adding
        # page-fault streams the machine could actually service
        workers = min(self.n_shards, max(1, os.cpu_count() or 1))
        self._pool = pool or ThreadPoolExecutor(
            max_workers=workers, thread_name_prefix="shard")
        self.set_splade_backend(self.params.splade_backend)
        self.set_rerank_backend(self.params.rerank_backend)

    # ------------------------------------------------------------------
    # group-wide knobs
    # ------------------------------------------------------------------
    def set_splade_backend(self, backend: str):
        """Switch every shard's stage-1 scorer (plans are keyed on the
        backend, so the next ``compile_plan`` recompiles)."""
        for sh in self.shards:
            sh.set_splade_backend(backend)
        self.splade_backend = backend

    def set_rerank_backend(self, backend: str):
        """Switch every shard's stage-4 tail. The *multi-shard* plans
        below keep the split tail structure regardless: the hybrid
        normaliser needs per-query statistics over the full cross-shard
        candidate list and the merge fuses need each shard's narrow
        score slice, so there is no single-dispatch tail to collapse
        into. Per-shard retrievers still honour the knob (their own
        plans are fused), and ``n_shards == 1`` delegates
        ``compile_plan`` wholesale — the one-shard group inherits the
        fused tail bitwise."""
        for sh in self.shards:
            sh.set_rerank_backend(backend)
        # group-level plans are split-shaped; the knob still keys caches
        self.rerank_backend = backend

    def splade_device_cache(self):
        """Materialise every shard's padded-postings device cache (each
        on its shard's device when one was assigned)."""
        return [sh.splade_device_cache() for sh in self.shards]

    def run_splade_batch(self, term_ids, term_weights, k=None,
                         backend=None, _record=True):
        """Group-wide stage 1: per-shard scoring + global merge. Kept
        for API completeness (benchmarks poke stage 1 directly); the
        serving paths go through the compiled plans."""
        k = self.params.first_k if k is None else k
        outs = list(self._pool.map(
            lambda i: self.shards[i].run_splade_batch(
                term_ids, term_weights, k, backend=backend,
                _record=_record),
            range(self.n_shards)))
        pids = np.concatenate(
            [np.where(p >= 0, p + self.offsets[i], -1)
             for i, (p, _) in enumerate(outs)], axis=1)
        scores = np.concatenate([s for _, s in outs], axis=1)
        live = self.live
        # n_shards == 1 shares the live object with its single shard,
        # whose own live path already merged the delta — skip it here
        if self.n_shards > 1 and live is not None and live.n_delta:
            d_pids, d_scores = live.splade_delta_topk(
                list(term_ids), list(term_weights), k)
            pids = np.concatenate([pids, d_pids], axis=1)
            scores = np.concatenate([scores, d_scores], axis=1)
        return merge_topk(pids, scores, k, pad_score=0.0)

    # ------------------------------------------------------------------
    # search entry points (n_shards == 1 delegates: bitwise-unsharded)
    # ------------------------------------------------------------------
    def search_batch_ctx(self, method, q_embs=None, term_ids=None,
                         term_weights=None, alpha=None, k=None, ctxs=None):
        # search and search_batch are inherited: they route through
        # here, so the one-shard delegation (and its ctx threading)
        # lands once
        if self.n_shards == 1:
            return self.shards[0].search_batch_ctx(
                method, q_embs=q_embs, term_ids=term_ids,
                term_weights=term_weights, alpha=alpha, k=k, ctxs=ctxs)
        return super().search_batch_ctx(method, q_embs=q_embs,
                                        term_ids=term_ids,
                                        term_weights=term_weights,
                                        alpha=alpha, k=k, ctxs=ctxs)

    def compile_plan(self, method: str) -> StagePlan:
        if self.n_shards == 1:
            return self.shards[0].compile_plan(method)
        return super().compile_plan(method)

    def attach_caches(self, caches):
        """Group-level caches only: the *merged* stage-1 rows are what
        get cached (shard-local rows carry shard-relative pids and must
        never alias the group's keys). With one shard every plan is
        delegated wholesale, so the caches follow the delegation."""
        self._caches = caches
        if self.n_shards == 1:
            self.shards[0].attach_caches(caches)

    def bump_index_generation(self):
        gen = super().bump_index_generation()
        for sh in self.shards:
            sh.index_generation = gen
        return gen

    def _plaid_salt(self) -> str:
        sp = self.shards[0].searcher.params
        return f"np{sp.nprobe}|cc{sp.candidate_cap}|nd{sp.ndocs}"

    # ------------------------------------------------------------------
    # live (mutable) index over the shard group
    # ------------------------------------------------------------------
    # Groups never take the unsharded inline-overlay route — the live
    # state is injected into the shared merge/fuse bodies at call time,
    # so per-shard plans stay frozen.
    _live_inline = False

    def enable_live(self):
        """Attach group-level live state. The delta segment and the
        tombstone set live at the coordinator; each shard retriever gets
        a :class:`~repro.index.live.LiveView` holding its own (local)
        tombstones so its SPLADE stage excludes them pre-top-k."""
        if self.live is not None:
            return self.live
        if self.n_shards == 1:
            self.live = self.shards[0].enable_live()
            return self.live
        if self.shards[0].searcher.device_resident:
            raise ValueError("live index requires the host (mmap) tier; "
                             "device_resident pools are frozen")
        from repro.index.live import LiveIndexState, LiveView
        live = LiveIndexState(self.shards[0].searcher.index,
                              self.shards[0].splade)
        # geometry is replicated across shards; the pid space is the
        # group's — new docs append past the last boundary
        live.base_n = self.n_docs
        for sh in self.shards:
            sh.live = LiveView()
        self.live = live
        return live

    def _sync_shard_view(self, j: int):
        lo, hi = int(self.offsets[j]), int(self.offsets[j + 1])
        self.shards[j].live.update(self.live.local_tombstones(lo, hi),
                                   generation=self.index_generation)

    def live_delete(self, gpid: int) -> bool:
        live = self._require_live()
        with self._live_mut:
            ok = live.delete(gpid)
            if not ok:
                return False
            gpid = int(gpid)
            if self.n_shards > 1 and gpid < live.base_n:
                j = int(np.searchsorted(self.offsets, gpid,
                                        side="right") - 1)
                self._sync_shard_view(j)
            self.bump_index_generation()
        return True

    def compact_live(self):
        """Merge the delta prefix into the **last** shard: delta doc j's
        global pid ``base_n + j`` already equals ``offsets[-1] + j``, so
        appending to the last shard's layout preserves every pid. The
        build runs off-gate; the swap (replace ``shards[-1]``, grow the
        boundary, rebase, bump) drains readers under the write gate."""
        if self.n_shards == 1:
            out = self.shards[0].compact_live()
            self.index_generation = self.shards[0].index_generation
            if out is not None:
                # mirror the grown layout (and drop plan closures built
                # over the pre-swap store's access stats)
                self.offsets[-1] += out["compacted"]
                self.n_docs = int(self.offsets[-1])
                with self._lock:
                    self._plans.clear()
            return out
        live = self._require_live()
        with self._live_mut:
            n_take = live.snapshot_delta()
            if n_take == 0:
                return None
            from repro.core.plaid import PLAIDSearcher
            from repro.index import live as live_mod
            from repro.index.builder import ColBERTIndex
            from repro.index.live import LiveView
            from repro.index.splade_index import SpladeIndex
            last = self.shards[-1]
            idx = last.searcher.index
            gen = self.index_generation + 1
            col_dir = idx.path.with_name(f"{idx.path.name}.g{gen}")
            spl_dir = idx.path.with_name(f"splade.g{gen}")
            live_mod.compact_colbert_dir(idx, live, n_take, col_dir)
            live_mod.compact_splade_dir(last.splade, live, n_take, spl_dir)
            new_searcher = PLAIDSearcher(
                ColBERTIndex(col_dir, mode=idx.store.mode),
                last.searcher.params, device_resident=False,
                device=last.searcher.device)
            new_retr = MultiStageRetriever(
                SpladeIndex.load(spl_dir), new_searcher,
                device=getattr(last, "device", None), params=self.params)
            new_retr.set_splade_backend(self.splade_backend)
            new_retr.set_rerank_backend(last.rerank_backend)
            with live.gate.write():
                j = self.n_shards - 1
                self.shards[j] = new_retr
                self.offsets[j + 1] += n_take   # plan closures see this
                self.n_docs = int(self.offsets[-1])
                with self._lock:
                    self._plans.clear()
                live.rebase(n_take)
                new_retr.live = LiveView()
                self._sync_shard_view(j)
                self.bump_index_generation()
        return {"compacted": n_take, "colbert_dir": str(col_dir),
                "splade_dir": str(spl_dir)}

    # ------------------------------------------------------------------
    # sharded stage plans
    # ------------------------------------------------------------------
    def _build_plan(self, method: str) -> StagePlan:
        """Compile the scatter-gather stage graph for one method.

        Stage discipline matches the unsharded plans (host stages touch
        only numpy; device dispatches and syncs live in device-kind
        stages), with two additions: per-shard stages carry
        ``fanout=n_shards`` and read/write the batch's shard axis, and
        ``merge_topk`` fuses run on the host over already-synced per-
        shard arrays."""
        p = self.params
        S = self.n_shards
        offs = self.offsets
        shards = self.shards
        dr = shards[0].searcher.device_resident
        gather_kind = DEVICE if dr else HOST
        access = None if dr else CombinedAccessStats(
            [sh.searcher.index.store.stats for sh in shards])
        ndocs = min(shards[0].searcher.params.ndocs,
                    shards[0].searcher.params.candidate_cap)

        if method == "colbert":
            from repro.core.plaid import (
                pad_query_batch,
                stage1_centroid_probe_batch,
                stage2_candidates_batch,
            )

            def probe(cb):
                # ONE centroid probe for the whole group: the centroid
                # set is replicated (geometry, not corpus), so a
                # per-shard probe would duplicate the einsum S times
                # for identical results
                sr = shards[0].searcher
                q, q_valid = pad_query_batch(cb.q_embs)
                B, q, q_valid = _pad_batch_rows(q, q_valid)
                scores_c, cids = stage1_centroid_probe_batch(
                    q, q_valid, sr.centroids, sr.params.nprobe)
                return cb.with_state(B=B, q=q, q_valid=q_valid,
                                     scores_c=scores_c, cids=cids)

            def candidates(cb, i):
                # per-shard candidate generation from the shard's IVF
                # slice; narrowed to the densest row's pow2 bucket (the
                # -1 fill is already compacted to the back) so the
                # codes gather and approx dispatch run at the shard's
                # ~cap/S occupancy, not the full global cap
                sr = shards[i].searcher
                cids = cb.state["cids"]
                if sr.device is not None:
                    cids = jax.device_put(cids, sr.device)
                cand = stage2_candidates_batch(
                    sr.ivf_padded, cids, sr.params.candidate_cap)
                cand_np = np.asarray(cand)
                n_real = (cand_np >= 0).sum(axis=1)
                W = min(_next_pow2(max(int(n_real.max()), 8)),
                        cand_np.shape[1])
                return {"cand": cand[:, :W], "cand_np": cand_np[:, :W],
                        "n_real": n_real}

            def gather_codes(cb, i):
                s = dict(cb.shard_states[i])
                if dr:
                    codes, valid = shards[i].searcher.gather_codes_batch(
                        s["cand"])
                else:
                    codes, _, valid = shards[i].searcher._dedup_gather(
                        s["cand_np"], codes_only=True)
                s.update(codes=codes, cvalid=valid)
                return s

            def approx(cb, i):
                # raw approximate scores, NOT a per-shard top-ndocs:
                # survivor selection must be global or a shard-local
                # ndocs cut would diverge from the single-index path
                s = dict(cb.shard_states[i])
                a = stage3_approx_score_batch(
                    cb.state["scores_c"], jnp.asarray(s["codes"]),
                    jnp.asarray(s["cvalid"]), cb.state["q_valid"])
                a = jnp.where(s["cand_np"] >= 0, a, -jnp.inf)
                s["approx_np"] = np.asarray(a)
                return s

            def merge_approx(cb):
                # live is read at call time: plans compiled before
                # enable_live (or before the first mutation) stay valid
                return merge_approx_state(cb, offs, ndocs, live=self.live)

            def gather_residuals(cb, i):
                s = dict(cb.shard_states[i])
                cols, sel = compact_owned(cb.state["final_g"],
                                          offs[i], offs[i + 1])
                if dr:
                    f_codes, f_packed, f_valid = \
                        shards[i].searcher.gather_tokens_batch(sel)
                else:
                    f_codes, f_packed, f_valid = \
                        shards[i].searcher._dedup_gather(
                            sel, codes_only=False)
                s.update(cols=cols, sel=sel, f_codes=f_codes,
                         f_packed=f_packed, f_valid=f_valid)
                return s

            def exact(cb, i):
                s = dict(cb.shard_states[i])
                st = cb.state
                ex = shards[i].searcher.exact_score_gathered(
                    st["q"], st["q_valid"], jnp.asarray(s["f_codes"]),
                    jnp.asarray(s["f_packed"]), jnp.asarray(s["f_valid"]),
                    jnp.asarray(s["sel"]))
                s["exact_np"] = np.asarray(ex)   # (Bp, W_i) narrow slice
                return s

            stages = (
                Stage("plaid_probe", DEVICE, probe),
                Stage("plaid_probe:ivf", DEVICE, candidates, fanout=S),
                Stage("host_gather:codes", gather_kind, gather_codes,
                      fanout=S, pooled=not dr),
                Stage("device_score:approx", DEVICE, approx, fanout=S),
                Stage("merge_topk:approx", HOST, merge_approx),
                Stage("host_gather:residuals", gather_kind,
                      gather_residuals, fanout=S, pooled=not dr),
                Stage("device_score:exact", DEVICE, exact, fanout=S),
                Stage("merge_topk", HOST,
                      lambda cb: fuse_colbert_state(cb, live=self.live)))
            return StagePlan(method=method, stages=stages,
                             access_stats=access, pool=self._pool)

        s1_kind = HOST if self.splade_backend == "host" else DEVICE
        backend = self.splade_backend

        def splade_stage(cb):
            """Group stage 1, writing the shard axis itself. On the
            device backends every shard's dispatch is issued *before*
            any sync (``dispatch_topk``/``finalize_topk``), so with
            per-shard device pinning the accelerators score their
            postings slices concurrently — a per-shard sync loop would
            serialise them behind the first shard's result."""
            cached = self._stage1_group_lookup(cb)
            if cached is not None:
                # merged rows for every query are cached: skip the
                # per-shard fanout; the merge stage rebuilds state
                return cb.with_state(stage1_cached=cached)
            tids, tw = list(cb.term_ids), list(cb.term_weights)
            live = self.live
            if backend == "host" or (live is not None and live.dirty):
                # a dirty live state forces the host stage-1: the shard
                # retrievers' live views apply tombstone exclusion
                # pre-top-k there (the device scorers have no exclusion
                # path), matching the unsharded live rule
                outs = [sh.run_splade_batch(tids, tw, p.first_k,
                                            _record=False)
                        for sh in shards]
            else:
                impl = shards[0]._splade_impl(backend)
                disps = [sh.splade_device_cache().dispatch_topk(
                    tids, tw, p.first_k, impl=impl) for sh in shards]
                outs = [sh.splade_device_cache().finalize_topk(d)
                        for sh, d in zip(shards, disps)]
            return cb.evolve(shard_states=tuple(
                {"pids": np.where(pd >= 0, pd + offs[i], -1),
                 "scores": sc}
                for i, (pd, sc) in enumerate(outs)))

        def fuse_splade(cb):
            cached = cb.state.get("stage1_cached")
            if cached is not None:
                pids_b, s_scores = cached
                return cb.evolve(pids=pids_b[:, :cb.k],
                                 scores=s_scores[:, :cb.k])
            cb = fuse_splade_state(cb, p.first_k, live=self.live)
            self._stage1_group_store(cb)
            return cb

        if method == "splade":
            stages = (Stage("splade_stage1", s1_kind, splade_stage),
                      Stage("merge_topk", HOST, fuse_splade))
            return StagePlan(method=method, stages=stages,
                             access_stats=access, pool=self._pool)

        # rerank / hybrid: merged SPLADE candidates → shard-parallel
        # residual gather → per-shard MaxSim → global fuse (+ α)
        def merge_stage1(cb):
            cached = cb.state.get("stage1_cached")
            if cached is not None:
                return stage1_state_from_rows(cb, *cached)
            cb = merge_stage1_state(cb, p.first_k, live=self.live)
            self._stage1_group_store(cb)
            return cb

        def gather(cb, i):
            st = cb.state
            cols, sel = compact_owned(st["gp"], offs[i], offs[i + 1])
            if dr:
                codes, packed, valid = \
                    shards[i].searcher.gather_tokens_batch(sel)
            else:
                codes, packed, valid = shards[i].searcher._dedup_gather(
                    sel, codes_only=False)
            return {"cols": cols, "sel": sel, "g_codes": codes,
                    "g_packed": packed, "g_valid": valid}

        def score(cb, i):
            s = dict(cb.shard_states[i])
            st = cb.state
            s["c_dev"] = shards[i].searcher.score_gathered_lazy(
                jnp.asarray(st["q"]), jnp.asarray(st["q_valid"]),
                jnp.asarray(s["g_codes"]), jnp.asarray(s["g_packed"]),
                jnp.asarray(s["g_valid"]), s["sel"])[:st["B"]]
            return s

        def fuse_rerank(cb):
            # sync each shard's narrow lazy score slice and scatter it
            # back into the global candidate columns
            return fuse_scatter_rerank(cb, method, p.normalizer,
                                       live=self.live)

        stages = (Stage("splade_stage1", s1_kind, splade_stage),
                  Stage("merge_topk:stage1", HOST, merge_stage1),
                  Stage("host_gather:residuals", gather_kind, gather,
                        fanout=S, pooled=not dr),
                  Stage("device_score:maxsim", DEVICE, score, fanout=S,
                        opens_async=True),
                  Stage("fuse_topk", DEVICE, fuse_rerank,
                        closes_async=True))
        return StagePlan(method=method, stages=stages,
                         access_stats=access, pool=self._pool)


def build_sharded_retriever(shard_dirs, boundaries, *, mode: str = "mmap",
                            plaid_params=None, multistage_params=None,
                            devices: Optional[Sequence] = None
                            ) -> ShardedRetriever:
    """Load a shard group written by ``split_index_tree`` into a
    :class:`ShardedRetriever`. ``shard_dirs``: per-shard directories
    each holding ``colbert/`` + ``splade/``; ``devices`` optionally
    pins shard i's device state (PLAID centroids and IVF, SPLADE device
    cache) to ``devices[i]`` — see ``launch.mesh.shard_device_map``."""
    from repro.core.plaid import PLAIDSearcher, PlaidParams
    from repro.index.builder import ColBERTIndex
    from repro.index.splade_index import SpladeIndex

    plaid_params = plaid_params or PlaidParams()
    shards = []
    for i, d in enumerate(shard_dirs):
        d = pathlib.Path(d)
        index = ColBERTIndex(d / "colbert", mode=mode)
        sidx = SpladeIndex.load(d / "splade", mmap=(mode == "mmap"))
        device = None if devices is None else devices[i]
        searcher = PLAIDSearcher(index, plaid_params, device=device)
        kw = {} if multistage_params is None \
            else {"params": multistage_params}
        retr = MultiStageRetriever(sidx, searcher, device=device, **kw)
        shards.append(retr)
    return ShardedRetriever(shards, boundaries)


# ---------------------------------------------------------------------------
# process-group backend: shared-nothing shard workers over RPC
# ---------------------------------------------------------------------------

#: Write ops mutate worker state, so the pure-op recovery machinery is
#: off-limits for them: hedging would race two applications of the same
#: write, and sibling failover could double-apply one that half-landed
#: on the failed replica. The dispatcher surfaces their failures to the
#: caller instead.
MUTATION_OPS = frozenset({"live_sync", "live_reload"})


class _Slot:
    """One logical RPC enqueued on a :class:`_ShardDispatcher`; resolves
    to either its own reply or its slice of a coalesced ``multi``
    reply. ``replica`` records which replica the flush landed on so the
    waiter can attribute success/failure and fail over to a sibling."""

    __slots__ = ("op", "payload", "cli", "rep", "index", "error",
                 "replica")

    def __init__(self, op: str, payload):
        self.op = op
        self.payload = payload
        self.cli = None
        self.rep = None               # None until flushed to the wire
        self.index = None             # position inside a multi dispatch
        self.error = None
        self.replica = None


class _ShardDispatcher:
    """Per-worker RPC coalescer: one dispatch per worker per stage.

    ``enqueue`` flushes immediately when the worker is idle (it should
    start computing as early as possible), and *buffers* while the
    worker has outstanding work — the worker serves FIFO one op at a
    time, so buffering behind an in-flight op costs zero worker idle,
    and every op that accumulates meanwhile rides the next flush as one
    ``multi`` frame (one encode, one send, one wakeup) instead of N.
    ``wait`` flushes anything still buffered — a slot can never
    strand — and demuxes per-op ok/error slices so one bad micro-batch
    doesn't poison its co-batched neighbours. Replies stay FIFO per
    connection, so the client's pipelined stream discipline is
    untouched."""

    def __init__(self, group: "ProcessShardGroup", index: int):
        self.group = group
        self.i = index
        self._lock = threading.Lock()
        self._buf: list = []
        self._last_cli = None
        self._last: dict = {}

    def enqueue(self, op: str, payload) -> _Slot:
        slot = _Slot(op, payload)
        with self._lock:
            replica, cli = self.group._route(self.i)  # fails fast dead
            self._buf.append(slot)
            if cli.outstanding() == 0:
                self._flush_locked(replica, cli)
        return slot

    def _flush_locked(self, replica, cli):
        from repro.serving.transport import ShardWorkerDied

        if not self._buf:
            return
        slots, self._buf = self._buf, []
        stats = self.group.pipeline_stats
        deadline_ms = self.group.op_deadline_ms
        try:
            if len(slots) == 1:
                s = slots[0]
                s.cli, s.rep = cli, cli.call_async(
                    s.op, s.payload, timeout_ms=deadline_ms)
                s.replica = replica
            else:
                rep = cli.call_async("multi", {"ops": [
                    {"op": s.op, "payload": s.payload} for s in slots]},
                    timeout_ms=deadline_ms)
                for j, s in enumerate(slots):
                    s.cli, s.rep, s.index = cli, rep, j
                    s.replica = replica
                stats.counter("rpc_coalesced_ops", len(slots) - 1)
        except ShardWorkerDied as e:
            # send failure (dead socket, injected fault): the client is
            # already marked dead. Park the error on every co-batched
            # slot instead of raising — waiters surface it inside their
            # failover handling, so multi-replica sets retry siblings
            # and single-replica sets raise at wait time as before.
            for s in slots:
                if s.rep is None:
                    s.error = e
                    s.replica = replica
            if replica is not None:
                self.group._replica_sets[self.i].record_failure(replica)
            return
        except BaseException as e:
            # non-connection failure: fan it out to every co-batched
            # slot (their waiters must fail, not re-flush an empty
            # buffer forever) and propagate
            for s in slots:
                if s.rep is None:
                    s.error = e
                    s.replica = replica
            raise
        stats.counter("rpc_dispatches")
        self._account(cli)

    def _account(self, cli):
        """Mirror the channel's monotonic byte counters into
        PipelineStats as deltas (a respawned client restarts at 0)."""
        ts = cli.transport_stats()
        if cli is not self._last_cli:
            self._last_cli, self._last = cli, {}
        for key in ("bytes_sent", "bytes_recv", "bytes_copied",
                    "bytes_zero_copy"):
            delta = ts[key] - self._last.get(key, 0)
            if delta > 0:
                self.group.pipeline_stats.counter(
                    f"transport_{key}", delta)
            self._last[key] = ts[key]

    def wait(self, slot: _Slot):
        from repro.serving.replica import _Straggler
        from repro.serving.transport import (DeadlineExceeded,
                                             ShardWorkerDied)

        if slot.rep is None and slot.error is None:
            with self._lock:
                if slot.rep is None and slot.error is None:
                    replica, cli = self.group._route(self.i)
                    self._flush_locked(replica, cli)
        g = self.group
        try:
            if slot.error is not None:
                raise slot.error
            out = g._wait_replica(self.i, slot)
            with self._lock:
                self._account(slot.cli)
            if slot.index is None:
                return out
            sub = out["replies"][slot.index]
            if not sub.get("ok", False):
                from repro.serving.transport import ShardWorkerError
                raise ShardWorkerError(
                    f"shard {self.i} op {slot.op!r} failed:\n"
                    f"{sub.get('error')}")
            return sub.get("result")
        except _Straggler:
            # the replica is merely slow: give up on it past the hedge
            # budget and re-run the op on a sibling (safe — shard ops
            # are pure; mutation ops never arm the budget, see
            # ``_wait_replica``). The straggler's reply stays pending on
            # its own connection; FIFO discipline consumes it later
            # without desequencing.
            g.pipeline_stats.counter("hedges")
            return g._resend_slot(self.i, slot)
        except (ShardWorkerDied, DeadlineExceeded) as e:
            if (slot.op in MUTATION_OPS
                    or g._replica_sets[self.i].total == 1):
                # mutations must not fail over (retry could double-
                # apply); single-replica keeps legacy heal-on-next-use
                raise
            g.pipeline_stats.counter("failover_retries")
            return g._resend_slot(self.i, slot, last_error=e)

    def call(self, op: str, payload):
        return self.wait(self.enqueue(op, payload))


class ProcessShardGroup(MultiStageRetriever):
    """Scatter-gather retriever whose shards are **separate OS
    processes** (``repro.serving.worker``), one per ``shards/<i>/``
    subtree, talked to over the layered ``repro.serving.transport``
    stack — shared-memory ring arenas (``transport="shm"``, tensor
    bytes cross zero-copy) or a socketpair stream (``"socket"``,
    portable), with per-worker RPC coalescing: ops that land on a busy
    worker ride the next flush as one ``multi`` frame, one dispatch per
    worker per stage across co-batched micro-batches.

    Shared-nothing is the point: each worker owns its mmap
    ``PagedStore`` segment (its *own page-cache working set* — the
    aggregate pool is split across processes, not replicated), its own
    SPLADE postings slice / device cache, and its own GIL, so per-shard
    gathers and kernels run truly concurrently on multi-core hosts —
    the regime where mmap scoring wins.

    Parity contract: workers execute the *same stage functions over the
    same inputs* as the in-process thread backend (the RPC codec is
    lossless for numpy dtypes), and every coordinator-side merge/fuse
    is the same shared function (:func:`merge_stage1_state`,
    :func:`fuse_scatter_rerank`, :func:`merge_approx_state`,
    :func:`fuse_colbert_state`) — so ``--shard-workers=process`` is
    bitwise-identical to ``--shard-workers=thread`` and therefore to
    ``shards=1``.

    Pipelining/backpressure: per-shard ``score`` dispatches are split
    into an ``opens_async`` send stage and a ``closes_async`` wait
    stage, so the executor's software pipelining parks a batch while
    its workers compute and runs the next batch's host stages — the
    same overlap semantics as lazy device dispatch, across a process
    boundary. Each in-flight micro-batch holds at most one outstanding
    RPC per worker, so the executor's admission semaphore bounds the
    RPC queue on every worker.

    Lifecycle: spawn-all at construction (first ping is the readiness
    barrier), heartbeat via :meth:`worker_health`, graceful SIGTERM
    drain (:meth:`close` escalates shutdown-RPC → SIGTERM → SIGKILL and
    always reaps — no orphans). A crashed worker fails its in-flight
    batch with :class:`~repro.serving.rpc.ShardWorkerDied` and is
    respawned on next use (single-restart healing: a worker that dies
    again before serving one successful call is not respawned)."""

    def __init__(self, shard_dirs, boundaries, *, mode: str = "mmap",
                 plaid_params=None, multistage_params=None,
                 spawn_timeout_s: float = 300.0,
                 call_timeout_s: float = 300.0,
                 worker_env: Optional[dict] = None,
                 transport: Optional[str] = None,
                 arena_bytes: Optional[int] = None,
                 replicas: int = 1,
                 replica_endpoints=None,
                 allow_degraded: bool = False,
                 op_deadline_ms: Optional[float] = None,
                 hedge_factor: float = 0.0,
                 hedge_floor_ms: float = 50.0,
                 failover_backoff_ms: float = 10.0,
                 fault_spec=None,
                 autostart: bool = True):
        from repro.core.plaid import PlaidParams
        from repro.launch.mesh import (default_shard_transport,
                                       shard_arena_bytes)
        from repro.serving.replica import ReplicaSet, _Replica
        from repro.serving.transport import FaultSpec

        self.shard_dirs = [str(d) for d in shard_dirs]
        if not self.shard_dirs:
            raise ValueError("empty shard group")
        self.offsets = np.asarray(boundaries, np.int64)
        if len(self.offsets) != len(self.shard_dirs) + 1:
            raise ValueError(
                f"{len(self.shard_dirs)} shards need "
                f"{len(self.shard_dirs) + 1} boundaries, "
                f"got {len(self.offsets)}")
        self.n_shards = len(self.shard_dirs)
        self.n_docs = int(self.offsets[-1])
        self.mode = mode
        self.plaid_params = plaid_params or PlaidParams()
        self.params = multistage_params or MultiStageParams()
        self.spawn_timeout_s = spawn_timeout_s
        self.call_timeout_s = call_timeout_s
        self.transport = transport or default_shard_transport()
        if self.transport not in ("shm", "socket"):
            raise ValueError(
                f"shard transport {self.transport!r} not in "
                f"('shm', 'socket')")
        self.arena_bytes = shard_arena_bytes(self.n_shards, arena_bytes)
        if worker_env is None:
            from repro.launch.mesh import shard_worker_env
            worker_env = shard_worker_env(self.n_shards)
        self._worker_env = worker_env
        self.allow_degraded = bool(allow_degraded)
        self.op_deadline_ms = op_deadline_ms
        self.failover_backoff_ms = float(failover_backoff_ms)
        self.fault_spec = (FaultSpec.parse(fault_spec)
                           if isinstance(fault_spec, str) else fault_spec)
        # replica axis: `replicas` local child workers per shard plus
        # any remote standalone endpoints; replicas[0] is the primary
        # slot the legacy single-replica semantics bind to
        n_local = int(replicas)
        endpoints = self._normalize_endpoints(replica_endpoints)
        if n_local < 0:
            raise ValueError(f"replicas {n_local} < 0")
        self._replica_sets = []
        for i in range(self.n_shards):
            reps = [_Replica(i, rid, self._client_factory(i, None))
                    for rid in range(n_local)]
            reps += [_Replica(i, n_local + j,
                              self._client_factory(i, ep), endpoint=ep)
                     for j, ep in enumerate(endpoints[i])]
            if not reps:
                raise ValueError(
                    f"shard {i} has no replicas (replicas=0 and no "
                    f"replica_endpoints entry)")
            self._replica_sets.append(ReplicaSet(
                i, reps, hedge_factor=hedge_factor,
                hedge_floor_ms=hedge_floor_ms))
        self._lock = threading.Lock()
        self._live_mut = threading.Lock()
        self._plans: dict = {}
        self.pipeline_stats = PipelineStats()
        total_replicas = sum(rs.total for rs in self._replica_sets)
        self._pool = ThreadPoolExecutor(
            max_workers=max(self.n_shards, total_replicas),
            thread_name_prefix="shard-rpc")
        self._disp = [_ShardDispatcher(self, i)
                      for i in range(self.n_shards)]
        self._closed = False
        self._healer = None
        self._heal_wake = threading.Event()
        self._centroids_cache = None
        self.set_splade_backend(self.params.splade_backend)
        # group plans are split-shaped (cross-process merges need each
        # worker's narrow score slice); the knob still resolves so the
        # plan-cache key and health snapshots stay uniform
        self.set_rerank_backend(self.params.rerank_backend)
        if autostart:
            self.start()

    def _normalize_endpoints(self, replica_endpoints):
        """Per-shard remote endpoint lists. Accepts None, a compact
        string (``;`` between shards, ``,`` between a shard's
        replicas), or an already-parsed sequence of sequences."""
        if replica_endpoints is None:
            return [[] for _ in range(self.n_shards)]
        if isinstance(replica_endpoints, str):
            parts = [p for p in replica_endpoints.split(";")]
            out = [[e.strip() for e in p.split(",") if e.strip()]
                   for p in parts]
        else:
            out = [list(p) for p in replica_endpoints]
        if len(out) != self.n_shards:
            raise ValueError(
                f"replica_endpoints covers {len(out)} shards, group "
                f"has {self.n_shards}")
        return out

    def _client_factory(self, i: int, endpoint):
        """Factory building an unspawned client for shard ``i`` at a
        given arena generation (a locator minted against a dead
        worker's arena can never resolve against the new one)."""
        import dataclasses as _dc

        def factory(generation: int):
            from repro.serving.rpc import ShardWorkerClient
            return ShardWorkerClient(
                i, self.shard_dirs[i], mode=self.mode,
                plaid_params=_dc.asdict(self.plaid_params),
                ms_params=_dc.asdict(self.params),
                env=self._worker_env,
                spawn_timeout_s=self.spawn_timeout_s,
                call_timeout_s=self.call_timeout_s,
                transport=self.transport,
                arena_bytes=self.arena_bytes,
                generation=generation,
                endpoint=endpoint,
                fault_spec=self.fault_spec)
        return factory

    # -- legacy single-replica views -----------------------------------
    @property
    def _clients(self) -> list:
        """Primary-replica clients, one per shard (the legacy view;
        sibling replicas live on ``_replica_sets``)."""
        return [rs.primary.client for rs in self._replica_sets]

    @property
    def restarts(self) -> list:
        return [rs.primary.restarts for rs in self._replica_sets]

    # ------------------------------------------------------------------
    # worker lifecycle
    # ------------------------------------------------------------------
    def start(self):
        """Spawn/connect every replica of every shard concurrently;
        returns after each one's readiness ping (jax imported, shard
        subtree mapped / remote worker answered). A replica that fails
        to come up tears the whole group down — a partially spawned
        group would leak the workers that did start."""
        def up(r):
            if r is self._replica_sets[r.shard_index].primary:
                return self._ensure_worker(r.shard_index)
            return r.ensure(fail_fast=False)

        try:
            list(self._pool.map(
                up, [r for rs in self._replica_sets
                     for r in rs.replicas]))
        except BaseException:
            self.close(grace_s=1.0)
            raise
        self._start_healer()
        return self

    def _ensure_worker(self, i: int):
        """Live *primary* client for shard ``i`` — the legacy
        single-replica contract, spawn-locked per replica so concurrent
        stages racing into a dead shard act exactly once.

        Crash discipline: a corpse discovered here is reaped and the
        discovering call **fails fast** with a clear
        :class:`~repro.serving.rpc.ShardWorkerDied` — a serving batch
        must not silently absorb a multi-second worker respawn. The
        *next* call respawns (heal-on-restart). A worker that dies
        again before serving one successful call — or that fails to
        spawn twice in a row — is quarantined (no respawn loop); a
        later successful call resets both budgets."""
        from repro.serving.rpc import ShardWorkerDied

        primary = self._replica_sets[i].primary
        with primary.lock:
            if self._closed:
                raise ShardWorkerDied(
                    f"shard group closed; shard {i} unavailable")
            return primary.ensure(fail_fast=True)

    def _route(self, i: int):
        """(replica, live client) to dispatch shard ``i``'s next frame
        on. Single-replica sets keep the legacy fail-fast primary path
        verbatim; multi-replica sets route fastest-healthy-first."""
        from repro.serving.rpc import ShardWorkerDied

        rs = self._replica_sets[i]
        if rs.total == 1:
            return rs.primary, self._ensure_worker(i)
        if self._closed:
            raise ShardWorkerDied(
                f"shard group closed; shard {i} unavailable")
        return rs.acquire()

    def _wait_replica(self, i: int, slot):
        """Wait one dispatched slot with health accounting. Raises
        ``_Straggler`` when a hedge budget expires with the reply still
        outstanding (the dispatcher re-sends on a sibling)."""
        from repro.serving.replica import _Straggler
        from repro.serving.transport import (DeadlineExceeded,
                                             ShardWorkerDied,
                                             ShardWorkerError)

        rs = self._replica_sets[i]
        r = slot.replica
        # mutation ops never arm the hedge budget: a straggling write
        # must be waited out, not re-sent to a sibling
        budget_ms = (None if slot.op in MUTATION_OPS
                     else rs.hedge_budget_ms(r))
        t0 = time.monotonic()
        try:
            if budget_ms is not None:
                try:
                    out = slot.cli.wait(slot.rep,
                                        timeout=budget_ms / 1e3,
                                        kill_on_timeout=False)
                except ShardWorkerError:
                    if not slot.rep.event.is_set():
                        raise _Straggler()  # slow, not failed
                    raise
            else:
                out = slot.cli.wait(slot.rep)
        except (ShardWorkerDied, DeadlineExceeded):
            if r is not None:
                rs.record_failure(r)
            raise
        if r is not None:
            rs.record_success(r, (time.monotonic() - t0) * 1e3)
        return out

    def _resend_slot(self, i: int, slot, last_error=None):
        """Re-run one slot's op on sibling replicas (exponential
        backoff + jitter between attempts). Shard ops are pure
        functions of the request, so a retry — even after a reply was
        maybe half-computed elsewhere — cannot change the answer."""
        import random as _random

        from repro.serving.transport import (DeadlineExceeded,
                                             ShardUnavailable,
                                             ShardWorkerDied,
                                             ShardWorkerError)

        if slot.op in MUTATION_OPS:
            # defense in depth behind the wait()-side guard: a write
            # may have half-applied on the failed replica, so re-running
            # it on a sibling could double-apply
            if last_error is not None:
                raise last_error
            raise ShardWorkerDied(
                f"shard {i}: mutation op {slot.op!r} is not retryable")
        rs = self._replica_sets[i]
        delay_s = self.failover_backoff_ms / 1e3
        exclude = slot.replica
        for _ in range(max(2, 2 * rs.total)):
            try:
                replica, cli = rs.acquire(exclude=exclude)
            except ShardUnavailable as e:
                # every *other* replica is unreachable right now — but
                # the excluded one (whose connection just faulted) may
                # merely need a reconnect, and a cooling sibling may
                # come back within the breaker window. Back off and let
                # the next iteration consider every replica again
                # instead of giving up while a live worker exists.
                exclude = None
                last_error = e.last_error or e
                time.sleep(delay_s * (1.0 + 0.5 * _random.random()))
                delay_s = min(delay_s * 2.0, 1.0)
                continue
            exclude = None     # after the first pick all siblings count
            t0 = time.monotonic()
            try:
                out = cli.call(slot.op, slot.payload,
                               timeout_ms=self.op_deadline_ms)
            except ShardWorkerError:
                raise          # deterministic op failure: do not retry
            except (ShardWorkerDied, DeadlineExceeded) as e:
                rs.record_failure(replica)
                last_error = e
                time.sleep(delay_s * (1.0 + 0.5 * _random.random()))
                delay_s = min(delay_s * 2.0, 1.0)
                continue
            rs.record_success(replica, (time.monotonic() - t0) * 1e3)
            return out
        raise ShardUnavailable(
            f"shard {i}: failover exhausted its retries "
            f"(last error: {last_error})", shard=i,
            last_error=last_error)

    def _degradable(self, fn):
        """Run one shard's stage op; with ``allow_degraded`` a shard
        whose every replica is gone yields None (its slot becomes a
        ``missing`` state) instead of failing the whole batch."""
        from repro.serving.transport import (DeadlineExceeded,
                                             ShardWorkerDied)

        try:
            return fn()
        except (ShardWorkerDied, DeadlineExceeded):
            if not self.allow_degraded:
                raise
            self.pipeline_stats.counter("degraded_shard_ops")
            return None

    # -- background healer ---------------------------------------------
    def _start_healer(self):
        """Replicated groups get a daemon that restores redundancy in
        the background (reconnect remote siblings, respawn local ones)
        instead of waiting for traffic to land on the dead replica.
        Single-replica groups keep the legacy heal-on-next-use path
        only — no extra thread, no behavior change."""
        if all(rs.total == 1 for rs in self._replica_sets):
            return
        self._healer = threading.Thread(target=self._healer_loop,
                                        name="shard-healer", daemon=True)
        self._healer.start()

    def _healer_loop(self):
        from repro.serving.transport import ShardWorkerDied

        while not self._closed:
            self._heal_wake.wait(1.0)
            if self._closed:
                return
            now = time.monotonic()
            for rs in self._replica_sets:
                for r in rs.replicas:
                    if self._closed:
                        return
                    if (r.is_alive() or r.quarantined()
                            or r.breaker_open_until > now):
                        continue
                    try:
                        r.ensure(fail_fast=False)
                        self.pipeline_stats.counter("replica_heals")
                    except ShardWorkerDied:
                        rs.record_failure(r)

    def _call_async(self, i: int, op: str, payload):
        cli = self._ensure_worker(i)
        return cli, cli.call_async(op, payload,
                                   timeout_ms=self.op_deadline_ms)

    def _wait(self, i: int, cli, rep):
        out = cli.wait(rep)
        rs = self._replica_sets[i]
        rs.record_success(rs.primary)         # healed / healthy
        return out

    def _call(self, i: int, op: str, payload):
        cli, rep = self._call_async(i, op, payload)
        return self._wait(i, cli, rep)

    def worker_pids(self) -> list:
        return [None if c is None else c.pid for c in self._clients]

    def heartbeat(self, timeout_s: float = 10.0) -> list:
        """Ping every worker; True per shard that answered."""
        from repro.serving.rpc import ShardWorkerDied, ShardWorkerError

        out = []
        for i, cli in enumerate(self._clients):
            if cli is None or not cli.alive():
                out.append(False)
                continue
            try:
                # soft deadline: a ping queued behind a long op must
                # not kill a busy worker
                cli.call("ping", {}, timeout=timeout_s,
                         kill_on_timeout=False)
                out.append(True)
            except (ShardWorkerDied, ShardWorkerError):
                out.append(False)
        return out

    def worker_health(self) -> list:
        """Per-worker vitals (pid, RSS, mmap segment bytes, served
        count, restart count, spawn/serve failure budgets, sibling
        replica state) — never raises, never respawns: a dead worker
        reports ``alive: False`` until traffic (or the healer thread)
        heals it."""
        from repro.serving.rpc import ShardWorkerDied, ShardWorkerError

        out = []
        for i, cli in enumerate(self._clients):
            rs = self._replica_sets[i]
            rec = {"shard": i,
                   "pid": None if cli is None else cli.pid,
                   "alive": bool(cli is not None and cli.alive()),
                   "restarts": self.restarts[i],
                   "spawn_failures": rs.primary.spawn_failures,
                   "serve_failures": rs.primary.serve_failures}
            if rs.total > 1:
                rec["replicas"] = [r.health() for r in rs.replicas]
                rec["alive_replicas"] = rs.alive_count()
            if cli is not None:
                ts = cli.transport_stats()
                rec["transport"] = ts["transport"]
                rec["rpc_bytes_sent"] = ts["bytes_sent"]
                rec["rpc_bytes_recv"] = ts["bytes_recv"]
                rec["rpc_bytes_copied"] = ts["bytes_copied"]
                rec["rpc_bytes_zero_copy"] = ts["bytes_zero_copy"]
                if cli.arena_generation is not None:
                    rec["arena_generation"] = cli.arena_generation
            if rec["alive"]:
                try:
                    # soft deadline (kill_on_timeout=False): health
                    # polls queue FIFO behind real work, and a monitor
                    # must never kill a worker that is merely busy
                    rec.update(cli.call("health", {}, timeout=10.0,
                                        kill_on_timeout=False))
                except ShardWorkerDied as e:
                    rec["alive"] = False
                    rec["error"] = str(e)
                except ShardWorkerError as e:
                    rec["busy"] = True
                    rec["error"] = str(e)
            out.append(rec)
        return out

    def transport_stats(self) -> dict:
        """Group-wide transport byte accounting: per-worker channel
        stats plus copied/zero-copy totals — how much tensor traffic
        actually bypassed serialization."""
        per, total = [], {"bytes_sent": 0, "bytes_recv": 0,
                          "bytes_copied": 0, "bytes_zero_copy": 0}
        for i, rs in enumerate(self._replica_sets):
            for r in rs.replicas:
                cli = r.client
                if cli is None:
                    continue
                ts = cli.transport_stats()
                ts["shard"] = i
                ts["replica"] = r.rid
                per.append(ts)
                for k in total:
                    total[k] += ts[k]
        return {"transport": self.transport, "per_worker": per,
                "total": total}

    def degraded_shards(self) -> list:
        """Shard ids currently served by zero live replicas — the set
        a degraded answer would be missing right now."""
        return [rs.i for rs in self._replica_sets
                if rs.alive_count() == 0]

    def close(self, grace_s: float = 5.0):
        """Graceful group shutdown: drain each worker (shutdown RPC,
        then SIGTERM, then SIGKILL) and reap every child; remote
        replicas just drop their connection (their accept loop serves
        the next coordinator). Idempotent. Takes each replica's spawn
        lock so a concurrent heal that was already past the
        closed-check finishes its spawn first and is then terminated
        here — never leaked."""
        with self._lock:
            if self._closed:
                return
            self._closed = True
        self._heal_wake.set()
        if self._healer is not None:
            self._healer.join(timeout=2.0)
        for rs in self._replica_sets:
            for r in rs.replicas:
                r.terminate(grace_s=grace_s)
        self._pool.shutdown(wait=False)

    # ------------------------------------------------------------------
    # retriever protocol
    # ------------------------------------------------------------------
    def run_splade_batch(self, term_ids, term_weights, k=None,
                         backend=None, _record=True):
        """Group-wide stage 1 over the worker processes (benchmarks
        poke this directly; serving goes through the compiled plans)."""
        k = self.params.first_k if k is None else k
        payload = {"term_ids": list(term_ids),
                   "term_weights": list(term_weights), "k": k,
                   "backend": backend or self.splade_backend}
        slots = [self._degradable(
                     lambda i=i: self._disp[i].enqueue("splade", payload))
                 for i in range(self.n_shards)]
        outs = [None if s is None else
                self._degradable(lambda i=i, s=s: self._disp[i].wait(s))
                for i, s in enumerate(slots)]
        live, _ = _live_shard_states(tuple(
            {"missing": True} if r is None else r for r in outs))
        pids = np.concatenate(
            [np.where(r["pids"] >= 0, r["pids"] + self.offsets[i], -1)
             for i, r in live], axis=1)
        scores = np.concatenate([r["scores"] for _, r in live], axis=1)
        return merge_topk(pids, scores, k, pad_score=0.0)

    def splade_device_cache(self):
        """Warm every worker's padded-postings device cache for the
        current stage-1 backend (no-op per worker on ``host``)."""
        slots = [self._degradable(
                     lambda i=i: self._disp[i].enqueue(
                         "warm", {"backend": self.splade_backend}))
                 for i in range(self.n_shards)]
        return [None if s is None else
                self._degradable(lambda i=i, s=s: self._disp[i].wait(s))
                for i, s in enumerate(slots)]

    def _centroids(self):
        """Replicated centroid geometry, loaded once from shard 0's
        subtree (metadata-sized; byte-identical across shards)."""
        if self._centroids_cache is None:
            import pathlib as _pl
            self._centroids_cache = jnp.asarray(np.load(
                _pl.Path(self.shard_dirs[0]) / "colbert"
                / "centroids.npy"))
        return self._centroids_cache

    def _plaid_salt(self) -> str:
        sp = self.plaid_params
        return f"np{sp.nprobe}|cc{sp.candidate_cap}|nd{sp.ndocs}"

    # ------------------------------------------------------------------
    # live (mutable) index over process workers
    # ------------------------------------------------------------------
    # The delta segment and the tombstone set live at the coordinator
    # (delta docs are scored coordinator-side via the merge bodies' live
    # injection); workers only need their local tombstones for SPLADE
    # pre-top-k exclusion, replicated by the ``live_sync`` write RPC.
    _live_inline = False

    def enable_live(self):
        """Attach coordinator-side live state; geometry is loaded from
        shard 0's subtree (replicated, metadata-sized). Remote replica
        endpoints are unsupported — delta replication is local-only."""
        if self.live is not None:
            return self.live
        for rs in self._replica_sets:
            for r in rs.replicas:
                if r.endpoint is not None:
                    raise ValueError(
                        "live index over remote replica endpoints is "
                        "unsupported (mutation replication is "
                        "local-only)")
        from repro.index.builder import ColBERTIndex
        from repro.index.live import LiveIndexState
        from repro.index.splade_index import SpladeIndex
        d = pathlib.Path(self.shard_dirs[0])
        live = LiveIndexState(ColBERTIndex(d / "colbert", mode="mmap"),
                              SpladeIndex.load(d / "splade", mmap=True))
        live.base_n = self.n_docs
        self.live = live
        return live

    def _broadcast_live_sync(self, j: int):
        """Full-state tombstone sync to every live replica of shard
        ``j`` — direct synchronous calls, never hedged or retried on
        siblings (``MUTATION_OPS``). A replica that is down right now
        is skipped; it re-syncs on the next mutation's broadcast
        (eventual consistency — quiesce-point parity only requires the
        replicas serving traffic to be current)."""
        payload = {"tombstones": self.live.local_tombstones(
                       int(self.offsets[j]), int(self.offsets[j + 1])),
                   "generation": self.index_generation}
        for r in self._replica_sets[j].replicas:
            cli = r.client
            if cli is None or not cli.alive():
                continue
            cli.call("live_sync", payload,
                     timeout_ms=self.op_deadline_ms)

    def live_delete(self, gpid: int) -> bool:
        live = self._require_live()
        with self._live_mut:
            ok = live.delete(gpid)
            if not ok:
                return False
            self.bump_index_generation()
            gpid = int(gpid)
            if gpid < live.base_n:
                j = int(np.searchsorted(self.offsets, gpid,
                                        side="right") - 1)
                self._broadcast_live_sync(j)
        return True

    def compact_live(self):
        """Merge the delta prefix into the last shard (pid-preserving —
        see :meth:`ShardedRetriever.compact_live`): build the new
        generation's subtree off-gate, then under the write gate grow
        the boundary, rebase, repoint ``shard_dirs[-1]`` (so respawns
        load the compacted layout) and ``live_reload`` every replica."""
        live = self._require_live()
        with self._live_mut:
            n_take = live.snapshot_delta()
            if n_take == 0:
                return None
            from repro.index import live as live_mod
            from repro.index.builder import ColBERTIndex
            from repro.index.splade_index import SpladeIndex
            last_dir = pathlib.Path(self.shard_dirs[-1])
            gen = self.index_generation + 1
            tree = last_dir.with_name(f"{last_dir.name}.g{gen}")
            col_dir, spl_dir = tree / "colbert", tree / "splade"
            live_mod.compact_colbert_dir(
                ColBERTIndex(last_dir / "colbert", mode="mmap"),
                live, n_take, col_dir)
            live_mod.compact_splade_dir(
                SpladeIndex.load(last_dir / "splade", mmap=True),
                live, n_take, spl_dir)
            j = self.n_shards - 1
            with live.gate.write():
                self.shard_dirs[j] = str(tree)
                self.offsets[j + 1] += n_take   # plan closures see this
                self.n_docs = int(self.offsets[-1])
                live.rebase(n_take)
                self.bump_index_generation()
                payload = {
                    "colbert_dir": str(col_dir),
                    "splade_dir": str(spl_dir),
                    "tombstones": live.local_tombstones(
                        int(self.offsets[j]), int(self.offsets[j + 1])),
                    "generation": self.index_generation}
                for r in self._replica_sets[j].replicas:
                    cli = r.client
                    if cli is None or not cli.alive():
                        continue
                    cli.call("live_reload", payload,
                             timeout_ms=self.op_deadline_ms)
                with self._lock:
                    self._plans.clear()
        return {"compacted": n_take, "colbert_dir": str(col_dir),
                "splade_dir": str(spl_dir)}

    # ------------------------------------------------------------------
    # RPC stage plans
    # ------------------------------------------------------------------
    def _build_plan(self, method: str) -> StagePlan:
        """Compile the scatter-gather stage graph with per-shard work
        delegated to the worker processes. Coordinator-side stages are
        the shared merge/fuse bodies; per-shard RPC stages are
        DEVICE-kind (the worker pool is this plan's compute resource —
        socket waits release the GIL exactly like a device sync)."""
        p = self.params
        S = self.n_shards
        offs = self.offsets
        backend = self.splade_backend
        ndocs = min(self.plaid_params.ndocs,
                    self.plaid_params.candidate_cap)

        if method == "colbert":
            from repro.core.plaid import (
                pad_query_batch,
                stage1_centroid_probe_batch,
            )
            nprobe = self.plaid_params.nprobe

            def probe(cb):
                # ONE centroid probe for the whole group (replicated
                # geometry), synced to host here so every downstream
                # stage ships plain numpy
                q, q_valid = pad_query_batch(cb.q_embs)
                B, q, q_valid = _pad_batch_rows(q, q_valid)
                scores_c, cids = stage1_centroid_probe_batch(
                    q, q_valid, self._centroids(), nprobe)
                return cb.with_state(
                    B=B, q=np.asarray(q), q_valid=np.asarray(q_valid),
                    scores_c=np.asarray(scores_c),
                    cids=np.asarray(cids))

            def candidates_rpc(cb, i):
                st = cb.state
                r = self._degradable(lambda: self._disp[i].call(
                    "colbert_candidates",
                    {"scores_c": st["scores_c"], "cids": st["cids"],
                     "q_valid": st["q_valid"]}))
                if r is None:
                    return {"missing": True}
                return {"cand_np": r["cand"], "approx_np": r["approx"],
                        "n_real": r["n_real"]}

            def exact_rpc(cb, i):
                st = cb.state
                cols, sel = compact_owned(st["final_g"],
                                          offs[i], offs[i + 1])
                r = self._degradable(lambda: self._disp[i].call(
                    "colbert_exact",
                    {"q": st["q"], "q_valid": st["q_valid"],
                     "sel": sel}))
                if r is None:
                    return {"missing": True}
                return {"cols": cols, "exact_np": r["scores"]}

            stages = (
                Stage("plaid_probe", DEVICE, probe),
                Stage("shard_rpc:candidates", DEVICE, candidates_rpc,
                      fanout=S, pooled=True),
                Stage("merge_topk:approx", HOST,
                      lambda cb: merge_approx_state(cb, offs, ndocs,
                                                    live=self.live)),
                Stage("shard_rpc:exact", DEVICE, exact_rpc,
                      fanout=S, pooled=True),
                Stage("merge_topk", HOST,
                      lambda cb: fuse_colbert_state(cb, live=self.live)))
            return StagePlan(method=method, stages=stages,
                             access_stats=None, pool=self._pool)

        def splade_stage(cb):
            """Group stage 1: every shard's request goes onto its wire
            *before* any reply is read (pipelined sockets), so all S
            worker processes score their postings slices concurrently —
            the process analogue of dispatch-all-then-sync-all. Under
            concurrent micro-batches the dispatcher coalesces the
            stage-1 ops that land on a busy worker into one frame."""
            cached = self._stage1_group_lookup(cb)
            if cached is not None:
                return cb.with_state(stage1_cached=cached)
            payload = {"term_ids": list(cb.term_ids),
                       "term_weights": list(cb.term_weights),
                       "k": p.first_k, "backend": backend}
            slots = [self._degradable(
                         lambda i=i: self._disp[i].enqueue("splade",
                                                           payload))
                     for i in range(S)]
            outs = [None if s is None else
                    self._degradable(
                        lambda i=i, s=s: self._disp[i].wait(s))
                    for i, s in enumerate(slots)]
            return cb.evolve(shard_states=tuple(
                {"missing": True} if r is None else
                {"pids": np.where(r["pids"] >= 0,
                                  r["pids"] + offs[i], -1),
                 "scores": r["scores"]}
                for i, r in enumerate(outs)))

        def fuse_splade(cb):
            cached = cb.state.get("stage1_cached")
            if cached is not None:
                pids_b, s_scores = cached
                return cb.evolve(pids=pids_b[:, :cb.k],
                                 scores=s_scores[:, :cb.k])
            cb = fuse_splade_state(cb, p.first_k, live=self.live)
            self._stage1_group_store(cb)
            return cb

        def merge_stage1(cb):
            cached = cb.state.get("stage1_cached")
            if cached is not None:
                return stage1_state_from_rows(cb, *cached)
            cb = merge_stage1_state(cb, p.first_k, live=self.live)
            self._stage1_group_store(cb)
            return cb

        if method == "splade":
            stages = (Stage("splade_stage1", DEVICE, splade_stage),
                      Stage("merge_topk", HOST, fuse_splade))
            return StagePlan(method=method, stages=stages,
                             access_stats=None, pool=self._pool)

        # rerank / hybrid: merged SPLADE candidates → per-shard RPC
        # (compacted gather + MaxSim inside the worker) → global fuse.
        # The dispatch/wait split is what preserves the executor's
        # software pipelining: the batch parks at the wait stage while
        # its S workers gather+score, and the coordinator runs the next
        # batch's host stages.
        def score_dispatch(cb, i):
            st = cb.state
            cols, sel = compact_owned(st["gp"], offs[i], offs[i + 1])
            slot = self._degradable(lambda: self._disp[i].enqueue(
                "score_tokens",
                {"q": st["q"], "q_valid": st["q_valid"], "sel": sel}))
            if slot is None:
                return {"missing": True}
            return {"cols": cols, "_slot": slot}

        def score_wait(cb, i):
            s = dict(cb.shard_states[i])
            if s.get("missing"):
                return s
            slot = s.pop("_slot")
            r = self._degradable(lambda: self._disp[i].wait(slot))
            if r is None:
                return {"missing": True}
            s["c_dev"] = r["scores"][:cb.state["B"]]
            return s

        stages = (
            Stage("splade_stage1", DEVICE, splade_stage),
            Stage("merge_topk:stage1", HOST, merge_stage1),
            Stage("shard_rpc:score", DEVICE, score_dispatch, fanout=S,
                  opens_async=True),
            Stage("shard_rpc:wait", DEVICE, score_wait, fanout=S,
                  closes_async=True),
            Stage("fuse_topk", HOST,
                  lambda cb: fuse_scatter_rerank(cb, method, p.normalizer,
                                                 live=self.live)))
        return StagePlan(method=method, stages=stages,
                         access_stats=None, pool=self._pool)


def build_shard_group(shard_dirs, boundaries, *, workers: str = "thread",
                      mode: str = "mmap", plaid_params=None,
                      multistage_params=None, devices=None,
                      transport=None, arena_bytes=None, **kw):
    """Load a shard group behind either worker backend.

    ``workers="thread"`` → in-process :class:`ShardedRetriever`
    (:func:`build_sharded_retriever`); ``workers="process"`` → one OS
    process per shard behind a :class:`ProcessShardGroup`. Both present
    the same retriever interface and return identical results.
    ``transport`` (process workers only): ``"shm"`` zero-copy ring
    arenas / ``"socket"`` in-frame segments; None picks the platform
    default (:func:`repro.launch.mesh.default_shard_transport`)."""
    if workers == "process":
        return ProcessShardGroup(shard_dirs, boundaries, mode=mode,
                                 plaid_params=plaid_params,
                                 multistage_params=multistage_params,
                                 transport=transport,
                                 arena_bytes=arena_bytes,
                                 **kw)
    if workers != "thread":
        raise ValueError(f"shard workers {workers!r} not in "
                         f"('thread', 'process')")
    return build_sharded_retriever(shard_dirs, boundaries, mode=mode,
                                   plaid_params=plaid_params,
                                   multistage_params=multistage_params,
                                   devices=devices)
