"""Live (mutable) index: append-mode delta segment + tombstones + compaction.

The frozen serve layout (PagedStore codes/residuals, IVF, SPLADE CSR)
never changes shape under traffic; mutability is layered beside it:

* **Upserts** residual-encode the new document against the *existing*
  centroids/codec (`kmeans.assign` + `encode_residuals` are per-row
  deterministic, so delta codes are bitwise-identical to what a
  from-scratch rebuild would assign the same embeddings) and append it
  to an in-RAM delta segment: per-doc centroid ids, packed residuals,
  SPLADE postings. Delta docs get append-only global pids
  ``base_n + j`` — stable across compactions, because a compaction
  promotes exactly the first ``n`` delta docs into the base in order.
* **Deletes** record the global pid in a tombstone set. Tombstoned
  docs stay physically present (base *and* compacted layouts) and are
  filtered at the merge stages (`merge_topk` / SPLADE top-k), which is
  what keeps pids stable and deletes O(1).
* **Compaction** merges the delta prefix into a *new* index directory
  (``<index>.g<gen>``) off-line, then atomically swaps the serve
  handles under a writer gate and bumps the index generation so the
  PR-9 exact/stage-1 caches invalidate.

Correctness bar (enforced by tests/test_live_index.py and the churn
soak): an interleaved upsert/delete/query trace returns bitwise-
identical top-k to a from-scratch rebuild of the surviving corpus at
every quiesce point, under the monotone pid map (surviving global pids,
ascending) ↔ (0..n_survivors-1).
"""

from __future__ import annotations

import functools
import json
import pathlib
import threading
from contextlib import contextmanager

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.store import PagedStore
from repro.index import ivf as ivf_mod
from repro.index import kmeans, residual
from repro.kernels.decompress_maxsim.ops import decompress_maxsim_scores_batch

# Candidate width of the exact delta dispatch when every row's delta
# candidates fit in it: a served hybrid row holds a few delta passages
# among its 200 candidates, so the dispatch scores one narrow kernel
# tile rather than the batch's full width. Set from the largest per-row
# delta count measured on the chip, rounded up to a power of two
# (PERF.md §6).
DELTA_WIDTH = 8


@functools.partial(jax.jit, static_argnames=("nbits",))
def encode_padded(emb, valid, centroids, cutoffs, nbits: int):
    """Residual-encode one passage padded to the index's ``doc_maxlen``:
    emb (doc_maxlen, dim) with its token validity (doc_maxlen,) →
    (cids (doc_maxlen,) int32, packed (doc_maxlen, pd) uint8), zero on
    the padding. One program for every passage length; each row is
    encoded as the per-length path encodes it (``kmeans.assign`` scores
    fixed chunks of rows, and the residual codec works row by row)."""
    cids, _ = kmeans.assign(emb, centroids)
    packed = residual.encode_residuals(emb, cids, centroids, cutoffs, nbits)
    return (jnp.where(valid, cids, 0),
            jnp.where(valid[:, None], packed, jnp.uint8(0)))


class RWGate:
    """Readers/writer gate with writer preference and re-entrant reads.

    A *first-entry* reader blocks while a writer holds **or waits for**
    the gate, so the compaction swap cannot starve under a saturating
    read load (new queries queue behind the waiting writer; in-flight
    ones drain). A thread already inside ``read()`` re-enters without
    touching the queue — the mixed-batch path recurses into
    ``search_batch_ctx`` — so writer preference can never deadlock a
    reader against itself (the depth is tracked per-thread).
    """

    def __init__(self):
        self._cond = threading.Condition()
        self._readers = 0
        self._writer = False
        self._writers_waiting = 0
        self._local = threading.local()

    @contextmanager
    def read(self):
        depth = getattr(self._local, "depth", 0)
        if depth:                      # nested read: already admitted
            self._local.depth = depth + 1
            try:
                yield
            finally:
                self._local.depth -= 1
            return
        with self._cond:
            while self._writer or self._writers_waiting:
                self._cond.wait()
            self._readers += 1
            self._local.depth = 1
        try:
            yield
        finally:
            with self._cond:
                self._readers -= 1
                self._local.depth = 0
                if not self._readers:
                    self._cond.notify_all()

    @contextmanager
    def write(self):
        with self._cond:
            self._writers_waiting += 1
            try:
                while self._writer or self._readers:
                    self._cond.wait()
                self._writer = True
            finally:
                self._writers_waiting -= 1
        try:
            yield
        finally:
            with self._cond:
                self._writer = False
                self._cond.notify_all()


class LiveView:
    """Shard-local view of the live state: the tombstones owned by one
    shard (local pids) plus counters — what a process worker needs to
    filter its own SPLADE stage. Delta docs never reach shard workers;
    they are scored at the coordinator."""

    gate = None

    def __init__(self, tombstones=None, generation: int = 0, counters=None):
        self.tombstones = np.sort(np.asarray(
            [] if tombstones is None else tombstones, np.int64).ravel())
        self.generation = int(generation)
        self.counters = dict(counters or {})

    def update(self, tombstones, generation=None, counters=None):
        """Replace the view wholesale (idempotent full-state sync)."""
        self.tombstones = np.sort(np.asarray(
            [] if tombstones is None else tombstones, np.int64).ravel())
        if generation is not None:
            self.generation = int(generation)
        if counters is not None:
            self.counters = dict(counters)

    @property
    def dirty(self) -> bool:
        return self.tombstones.size > 0

    @property
    def base_exclude(self) -> np.ndarray:
        return self.tombstones

    def stats(self) -> dict:
        out = {"tombstones": int(self.tombstones.size),
               "delta_docs": 0, "generation": self.generation}
        out.update(self.counters)
        return out


class LiveIndexState:
    """Owner-side mutable state: the delta segment, the tombstone set,
    the compaction gate, and the delta scoring primitives the serve
    paths compose (all bitwise-matched to their frozen counterparts)."""

    def __init__(self, index, splade):
        self.base_n = int(index.n_docs)
        self.doc_maxlen = int(index.doc_maxlen)
        self.dim = int(index.dim)
        self.nbits = int(index.nbits)
        self.packed_dim = int(index.store.packed_dim)
        self.n_centroids = int(index.n_centroids)
        self.quantum = float(splade.quantum)
        self.vocab = int(splade.vocab)
        self._centroids_j = jnp.asarray(index.centroids)
        self._cutoffs_j = jnp.asarray(index.bucket_cutoffs)
        self._bweights_j = jnp.asarray(index.bucket_weights)

        # append-only delta segment (per-doc arrays, list index = local pid)
        self._cids: list[np.ndarray] = []
        self._packed: list[np.ndarray] = []
        self._doclens: list[int] = []
        self._term_ids: list[np.ndarray] = []
        self._term_weights: list[np.ndarray] = []

        self._tomb: set[int] = set()
        self._tomb_arr = np.zeros(0, np.int64)
        self._tomb_dirty = False

        self.gate = RWGate()
        self._lock = threading.Lock()
        self.counters = {"upserts": 0, "deletes": 0, "compactions": 0,
                         "docs_compacted": 0}

        # lazy caches keyed on (base_n, n_delta)
        self._splade_cache = (None, None)
        self._ivf_cache = (None, None)

    # -- mutation ----------------------------------------------------------
    def encode_doc(self, doc_emb, doc_len=None):
        """Residual-encode one document against the frozen geometry.
        Returns (cids (L,) int32, packed (L, pd) uint8, L)."""
        emb = np.asarray(doc_emb, np.float32)
        if emb.ndim != 2 or emb.shape[1] != self.dim:
            raise ValueError(f"doc_emb must be (L, {self.dim}), got {emb.shape}")
        L = int(emb.shape[0] if doc_len is None else doc_len)
        if not (0 < L <= self.doc_maxlen):
            raise ValueError(f"doc_len {L} outside (0, {self.doc_maxlen}]")
        padded = np.zeros((self.doc_maxlen, self.dim), np.float32)
        padded[:L] = emb[:L]
        cids, packed = encode_padded(
            jnp.asarray(padded), jnp.asarray(np.arange(self.doc_maxlen) < L),
            self._centroids_j, self._cutoffs_j, self.nbits)
        return (np.asarray(cids, np.int32)[:L],
                np.asarray(packed, np.uint8)[:L], L)

    def upsert(self, doc_emb, term_ids, term_weights, doc_len=None) -> int:
        """Append one document to the delta segment → its global pid."""
        cids, packed, L = self.encode_doc(doc_emb, doc_len)
        t = np.asarray(term_ids, np.int32).ravel()
        w = np.asarray(term_weights, np.float32).ravel()
        with self._lock:
            j = len(self._doclens)
            self._cids.append(cids)
            self._packed.append(packed)
            self._doclens.append(L)
            self._term_ids.append(t)
            self._term_weights.append(w)
            self.counters["upserts"] += 1
            return self.base_n + j

    def delete(self, gpid: int) -> bool:
        """Tombstone a global pid. False if unknown or already deleted."""
        gpid = int(gpid)
        with self._lock:
            if gpid < 0 or gpid >= self.base_n + len(self._doclens):
                return False
            if gpid in self._tomb:
                return False
            self._tomb.add(gpid)
            self._tomb_dirty = True
            self.counters["deletes"] += 1
            return True

    # -- introspection -----------------------------------------------------
    @property
    def n_delta(self) -> int:
        return len(self._doclens)

    @property
    def dirty(self) -> bool:
        return bool(self._doclens) or bool(self._tomb)

    def tombstone_array(self) -> np.ndarray:
        """Sorted int64 snapshot of all tombstoned global pids."""
        with self._lock:
            if self._tomb_dirty:
                self._tomb_arr = np.array(sorted(self._tomb), np.int64)
                self._tomb_dirty = False
            return self._tomb_arr

    @property
    def base_exclude(self) -> np.ndarray:
        """Tombstoned *base* pids (for SPLADE score exclusion)."""
        t = self.tombstone_array()
        return t[t < self.base_n]

    def local_tombstones(self, lo: int, hi: int) -> np.ndarray:
        """Tombstoned pids within [lo, hi), shifted to shard-local."""
        t = self.tombstone_array()
        return t[(t >= lo) & (t < hi)] - lo

    def is_tombstoned(self, gpids) -> np.ndarray:
        """Vectorised tombstone membership for a global pid array."""
        return np.isin(np.asarray(gpids), self.tombstone_array())

    def stats(self) -> dict:
        with self._lock:
            out = dict(self.counters)
            out["delta_docs"] = len(self._doclens)
            out["delta_tokens"] = int(sum(self._doclens))
            out["tombstones"] = len(self._tomb)
        return out

    # -- SPLADE delta ------------------------------------------------------
    def _delta_splade(self, n: int):
        key, idx = self._splade_cache
        if key == (self.base_n, n):
            return idx
        from repro.index.splade_index import build_splade_index
        T = max(int(t.size) for t in self._term_ids[:n]) if n else 1
        ids = np.full((n, max(T, 1)), -1, np.int32)
        ws = np.zeros((n, max(T, 1)), np.float32)
        for j in range(n):
            t = self._term_ids[j]
            ids[j, :t.size] = t
            ws[j, :t.size] = self._term_weights[j]
        # the base quantum is pinned so delta impacts are bitwise what a
        # full rebuild (same quantum) would produce for these docs
        idx = build_splade_index(ids, ws, self.vocab, n, quantum=self.quantum)
        self._splade_cache = ((self.base_n, n), idx)
        return idx

    def splade_delta_topk(self, term_ids, term_weights, k: int):
        """Delta-only SPLADE top-k → ((B, k) global pids, (B, k) scores),
        padded (-1, 0.0); tombstoned delta docs excluded pre-top-k."""
        n = self.n_delta
        B = len(term_ids)
        if n == 0:
            return (np.full((B, k), -1, np.int64),
                    np.zeros((B, k), np.float32))
        t = self.tombstone_array()
        excl = t[t >= self.base_n] - self.base_n
        excl = excl[excl < n]
        pids_l, scores = self._delta_splade(n).score_batch_host(
            term_ids, term_weights, k, exclude=excl)
        pids = np.where(pids_l >= 0, pids_l.astype(np.int64) + self.base_n,
                        np.int64(-1))
        return pids, scores

    # -- PLAID delta -------------------------------------------------------
    def _delta_ivf(self, n: int) -> dict:
        key, d = self._ivf_cache
        if key == (self.base_n, n):
            return d
        d = {}
        for j in range(n):
            for c in np.unique(self._cids[j]).tolist():
                d.setdefault(int(c), []).append(j)
        d = {c: np.asarray(js, np.int64) for c, js in d.items()}
        self._ivf_cache = ((self.base_n, n), d)
        return d

    def delta_candidates(self, cids_np) -> list:
        """cids_np (B, Lq, nprobe) probed centroid ids → per-query sorted
        unique *global* delta candidate pids (tombstoned excluded)."""
        n = self.n_delta
        cids_np = np.asarray(cids_np)
        B = cids_np.shape[0]
        if n == 0:
            return [np.zeros(0, np.int64) for _ in range(B)]
        iv = self._delta_ivf(n)
        t = self.tombstone_array()
        excl = set((t[t >= self.base_n] - self.base_n).tolist())
        out = []
        for b in range(B):
            probed = np.unique(cids_np[b]).tolist()
            locs = [iv[c] for c in probed if c in iv]
            if not locs:
                out.append(np.zeros(0, np.int64))
                continue
            uniq = np.unique(np.concatenate(locs))
            if excl:
                uniq = uniq[~np.isin(uniq, np.array(sorted(excl), np.int64))]
            out.append(uniq + self.base_n)
        return out

    def _gather_delta(self, pids_mat, with_packed: bool):
        """(B, C) global delta pids (-1 pad) → (codes (B, C, Ld),
        packed (B, C, Ld, pd) | None, valid (B, C, Ld)) — the delta
        twin of ``PLAIDSearcher._dedup_gather``."""
        pids_mat = np.asarray(pids_mat)
        mask = pids_mat >= 0
        local = np.where(mask, pids_mat - self.base_n, 0).astype(np.int64)
        uniq = np.unique(local[mask]) if mask.any() else np.zeros(1, np.int64)
        Ld = self.doc_maxlen
        U = len(uniq)
        codes_u = np.zeros((U, Ld), np.int32)
        valid_u = np.zeros((U, Ld), bool)
        packed_u = (np.zeros((U, Ld, self.packed_dim), np.uint8)
                    if with_packed else None)
        for i, j in enumerate(uniq.tolist()):
            if 0 <= j < len(self._doclens):
                L = self._doclens[j]
                codes_u[i, :L] = self._cids[j]
                valid_u[i, :L] = True
                if with_packed:
                    packed_u[i, :L] = self._packed[j]
        pos = np.minimum(np.searchsorted(uniq, local), U - 1)
        codes = codes_u[pos]
        valid = valid_u[pos] & mask[..., None]
        packed = packed_u[pos] if with_packed else None
        return codes, packed, valid

    def approx_scores(self, scores_c, q_valid, pids_mat) -> np.ndarray:
        """Stage-3 centroid-interaction scores for delta candidates,
        -inf at -1 slots — bitwise the frozen ``approx`` for the same
        docs (same ``stage3_approx_score_batch``, same masking)."""
        from repro.core.plaid import stage3_approx_score_batch
        pids_mat = np.asarray(pids_mat)
        codes, _, valid = self._gather_delta(pids_mat, with_packed=False)
        approx = stage3_approx_score_batch(
            jnp.asarray(scores_c), jnp.asarray(codes), jnp.asarray(valid),
            jnp.asarray(q_valid))
        return np.where(pids_mat >= 0, np.asarray(approx), -np.inf).astype(
            np.float32)

    def delta_width(self, pids_mat) -> int:
        """Candidate width of the exact delta dispatch for a (B, C)
        matrix of delta pids (-1 elsewhere): ``DELTA_WIDTH`` where every
        row's delta pids fit in it and it is narrower than C, else C."""
        pids_mat = np.asarray(pids_mat)
        C = pids_mat.shape[1]
        most = int((pids_mat >= 0).sum(axis=1).max(initial=0))
        return DELTA_WIDTH if most <= DELTA_WIDTH < C else C

    def exact_scores(self, q, q_valid, pids_mat, width=None) -> np.ndarray:
        """Exact decompress+MaxSim for the delta pids of a (B, C) matrix,
        -inf at -1 slots. Each row's delta pids are packed to the left
        of a (B, ``width``) matrix, gathered and scored there, and their
        scores scattered back to their columns; ``width`` is the caller's
        :meth:`delta_width` of ``pids_mat`` (computed here when None).
        The kernel and argument layout are
        ``PLAIDSearcher.score_gathered_lazy``'s and a candidate's score
        depends on no other column, so per-candidate scores are bitwise
        what the frozen path computes."""
        pids_mat = np.asarray(pids_mat)
        W = self.delta_width(pids_mat) if width is None else width
        # a stable sort puts each row's delta columns first, in order
        cols = np.argsort(pids_mat < 0, axis=1, kind="stable")[:, :W]
        narrow = np.take_along_axis(pids_mat, cols, axis=1)
        codes, packed, valid = self._gather_delta(narrow, with_packed=True)
        # host arrays go to the jitted call as they are: it copies them
        # to the device in less time than a jnp.asarray apiece
        scores = decompress_maxsim_scores_batch(
            q, packed, codes, valid, self._centroids_j, self._bweights_j,
            nbits=self.nbits, q_valid=q_valid)
        out = np.full(pids_mat.shape, -np.inf, np.float32)
        np.put_along_axis(out, cols, np.where(narrow >= 0, np.asarray(
            scores), -np.inf), axis=1)
        return out

    # -- compaction --------------------------------------------------------
    def snapshot_delta(self) -> int:
        """Number of delta docs safe to compact (append-only prefix)."""
        with self._lock:
            return len(self._doclens)

    def rebase(self, n_take: int):
        """Drop the compacted prefix and advance base_n. Global pids are
        unchanged (delta doc j becomes base doc base_n + j)."""
        with self._lock:
            del self._cids[:n_take]
            del self._packed[:n_take]
            del self._doclens[:n_take]
            del self._term_ids[:n_take]
            del self._term_weights[:n_take]
            self.base_n += n_take
            self.counters["compactions"] += 1
            self.counters["docs_compacted"] += n_take
            self._splade_cache = (None, None)
            self._ivf_cache = (None, None)


# --------------------------------------------------------------------------
# compaction: delta prefix → new on-disk index directories
# --------------------------------------------------------------------------

def compact_colbert_dir(index, live: LiveIndexState, n_take: int, out_dir):
    """Write a new ColBERT index dir = base + first ``n_take`` delta
    docs. Geometry (centroids/codec) is copied verbatim; codes/residuals
    are concatenated (delta rows were encoded with the same geometry,
    so the result is bitwise what the from-scratch builder produces for
    the concatenated corpus); the IVF is rebuilt over the full layout.
    """
    out = pathlib.Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    base_codes = np.asarray(index.store.codes)
    base_res = np.asarray(index.store.residuals)
    d_cids = [live._cids[j] for j in range(n_take)]
    d_packed = [live._packed[j] for j in range(n_take)]
    d_lens = np.asarray([live._doclens[j] for j in range(n_take)], np.int32)

    codes = np.concatenate([base_codes] + d_cids) if n_take else base_codes
    res = np.vstack([base_res] + d_packed) if n_take else base_res
    PagedStore.write(out, codes, res, dim=index.dim, nbits=index.nbits)

    np.save(out / "centroids.npy", np.asarray(index.centroids))
    np.save(out / "bucket_cutoffs.npy", np.asarray(index.bucket_cutoffs))
    np.save(out / "bucket_weights.npy", np.asarray(index.bucket_weights))
    doclens = np.concatenate([np.asarray(index.doclens, np.int32), d_lens])
    n_docs = len(doclens)
    np.save(out / "doclens.npy", doclens)
    offsets = np.zeros(n_docs + 1, np.int64)
    np.cumsum(doclens, out=offsets[1:])
    np.save(out / "doc_offsets.npy", offsets)

    token_pids = np.repeat(np.arange(n_docs), doclens)
    iv = ivf_mod.build_ivf(codes, token_pids, index.n_centroids)
    iv.pids.tofile(out / "ivf_pids.bin")
    np.save(out / "ivf_offsets.npy", iv.offsets)

    meta = json.loads((out / "meta.json").read_text())
    meta.update({"n_docs": int(n_docs), "doc_maxlen": int(index.doc_maxlen),
                 "n_centroids": int(index.n_centroids)})
    (out / "meta.json").write_text(json.dumps(meta))

    # tombstones ride along for operators / cold restarts; serving keeps
    # them in RAM (pids are stable, so the set survives the swap as-is)
    np.save(out / "tombstones.npy", live.tombstone_array())
    return out


def compact_splade_dir(splade, live: LiveIndexState, n_take: int, out_dir):
    """Write a new SPLADE CSR dir = base postings + first ``n_take``
    delta docs' postings, re-sorted into the builder's (term, pid)
    order and quantised with the *base* quantum — bitwise the CSR a
    from-scratch build (pinned quantum) produces for the same corpus."""
    from repro.index.splade_index import SpladeIndex
    base_terms = np.repeat(np.arange(splade.vocab, dtype=np.int64),
                           np.diff(splade.term_offsets))
    base_pids = np.asarray(splade.pids, np.int64)
    base_imps = np.asarray(splade.impacts, np.uint8)

    ts, ps, ims = [base_terms], [base_pids], [base_imps]
    for j in range(n_take):
        t = live._term_ids[j]
        w = live._term_weights[j]
        keep = w > 0  # the same filter build_splade_index applies
        t, w = t[keep].astype(np.int64), w[keep]
        imp = np.clip(np.round(w / max(live.quantum, 1e-9)), 1, 255)
        ts.append(t)
        # local pid within *this* CSR: delta doc j lands after the base
        # docs of the segment being compacted (== live.base_n + j only
        # in the unsharded case; a shard group compacts into its last
        # shard, whose local base count is splade.n_docs)
        ps.append(np.full(t.shape, splade.n_docs + j, np.int64))
        ims.append(imp.astype(np.uint8))
    terms = np.concatenate(ts)
    pids = np.concatenate(ps)
    imps = np.concatenate(ims)
    order = np.lexsort((pids, terms))
    terms, pids, imps = terms[order], pids[order], imps[order]
    counts = np.bincount(terms, minlength=splade.vocab)
    offsets = np.zeros(splade.vocab + 1, np.int64)
    np.cumsum(counts, out=offsets[1:])
    idx = SpladeIndex(term_offsets=offsets, pids=pids.astype(np.int32),
                      impacts=imps, quantum=float(splade.quantum),
                      n_docs=int(splade.n_docs + n_take),
                      vocab=int(splade.vocab))
    idx.save(out_dir)
    return pathlib.Path(out_dir)


# --------------------------------------------------------------------------
# rebuild oracle helpers (tests + churn soak)
# --------------------------------------------------------------------------

def map_global_to_ref(pids, survivors: np.ndarray):
    """Map global pids → reference (from-scratch rebuild) pids under
    the monotone bijection sorted(survivors) ↔ 0..n-1. -1 passes
    through. The map is monotone, so (score desc, pid asc) tie order —
    the total order every merge in this codebase uses — is preserved,
    and mapped top-k lists compare exactly."""
    pids = np.asarray(pids)
    out = np.full(pids.shape, -1, np.int64)
    m = pids >= 0
    out[m] = np.searchsorted(survivors, pids[m])
    return out


def build_reference_indexes(colbert_dir, splade_dir, doc_embs, doc_lens,
                            term_ids, term_weights, vocab, *,
                            centroids, bucket_cutoffs, bucket_weights,
                            nbits: int, quantum: float):
    """From-scratch rebuild of a (surviving) corpus with the serve
    index's frozen geometry pinned — the parity oracle."""
    from repro.index.builder import build_colbert_index
    from repro.index.splade_index import build_splade_index
    build_colbert_index(colbert_dir, np.asarray(doc_embs, np.float32),
                        np.asarray(doc_lens), nbits=nbits,
                        centroids=centroids, bucket_cutoffs=bucket_cutoffs,
                        bucket_weights=bucket_weights)
    spl = build_splade_index(np.asarray(term_ids), np.asarray(term_weights),
                             vocab, len(np.asarray(doc_lens)),
                             quantum=quantum)
    spl.save(splade_dir)
    return colbert_dir, splade_dir


class AutoCompactor(threading.Thread):
    """Background thread: compact when the delta segment crosses a
    threshold. Single-flight by construction (the only caller of
    ``compact_live`` on its retriever)."""

    def __init__(self, retriever, every: int, interval_s: float = 0.25):
        super().__init__(daemon=True, name="live-compactor")
        self.retriever = retriever
        self.every = int(every)
        self.interval_s = interval_s
        # not ``_stop``: that name is a ``threading.Thread`` method,
        # which ``join`` calls once the thread has ended
        self._stop_event = threading.Event()

    def run(self):
        while not self._stop_event.wait(self.interval_s):
            live = getattr(self.retriever, "live", None)
            if live is not None and live.n_delta >= self.every:
                try:
                    self.retriever.compact_live()
                except Exception:  # pragma: no cover - surfaced via health
                    import traceback
                    traceback.print_exc()

    def stop(self):
        self._stop_event.set()
        self.join(timeout=5)
