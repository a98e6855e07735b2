"""Plain float64 reference and the comparison that decides ``correct``.

It imports nothing of the program. It draws the corpus's SPLADE terms
and the queries again from the seed (``gen``), and reads the compressed
ColBERT index that set-up wrote — per-token centroid ids, packed
residual codes, the centroid table and the bucket weights, the data a
deployment serves — with its own reader. From these it computes, per
sampled request, what the served path must answer:

* ``hybrid``: SPLADE scores of every passage from the uint8 impacts
  (global quantum = largest weight / 255), the top ``first_k`` by score
  then pid, exact MaxSim of each candidate over its decoded tokens
  (centroid + bucket weight per dimension), z-normalised fusion at
  ``alpha`` over the candidate list, and the top ``k``;
* ``colbert`` (PLAID): centroid scores, the top ``nprobe`` centroids of
  every query token, every passage holding a token of one of them,
  their approximate score (the sum over query tokens of the best
  centroid score among the passage's tokens), the ``ndocs / 4`` best,
  exact MaxSim, and the top ``k``.

Every float32 step of the served path can move a score by its rounding,
so a candidate near a cut (the ``first_k``-th SPLADE score, a query
token's ``nprobe``-th centroid, the last survivor's approximate score) may
fall either way. The reference bounds each score's float32 error and
keeps both outcomes where the bounds overlap: a served answer is judged
against every admissible candidate list and passes on the one it fits
best. Four numbers come out of a sample of answers:

* ``failed``: requests that got an error, no reply, or a refused
  connection;
* ``bad_pids``: answer pids that are not an admissible candidate or
  survivor, repeated, or missing;
* ``score_err``: the worst gap between a served score and the
  reference's, as a share of the score's float32 forward-error bound;
* ``rank_gap``: the worst amount, in the same unit, by which the
  reference score of the pid served at rank j falls below the j-th best
  reference score of the candidates.

Under writes (traffic with ``writes``; hybrid only) the corpus a query
sees depends on when it ran. The load generator sends its writes in
order on one connection, one at a time, so the writes applied at any
moment are a prefix of the write log. A query must see every write
acknowledged before it was sent, and no write sent after its reply
arrived; each prefix in between is an admissible state, and the answer
is judged against every outcome of every such state. A deleted pid is
not admissible in a state that holds its delete, an upserted one only
in a state that holds its upsert, so an acknowledged upsert that
belongs in the top k and is not served counts in ``rank_gap``. The
upserted passages are drawn again from the seed (``gen``) and encoded
by the reference itself (``Versions``); their SPLADE impacts take the
base corpus's quantum, as the program pins it.
"""

from __future__ import annotations

import itertools
import pathlib
from concurrent.futures import ThreadPoolExecutor

import numpy as np

import gen
import harness
import stats

U32 = 2.0 ** -24          # float32 unit roundoff
# Relative error of a product in the upsert encoder's centroid scores: a
# float32 einsum at the backend's default precision, which on the TPU
# rounds each operand to bfloat16 (8 significant bits, so off by less
# than 2^-7 of its value whether rounded or truncated): at most
# 2·2^-7 + 2^-14 of the product's size. The float32 sum adds at most
# dim·2^-24 of the sum of the products' sizes.
ENCODE_ROUNDING = 2 * 2.0 ** -7 + 2.0 ** -14
ENCODE_ROWS = 256         # tokens encoded per block
BOUNDARY_WINDOW = 48      # ranks on each side of a cut searched for swaps
MAX_ALTERNATIVES = 256
THREADS = 8               # answers are judged in parallel; NumPy drops the GIL


class Index:
    """The stored ColBERT index, read without the program: token rows of
    passage p are ``offsets[p]:offsets[p+1]`` (passages in pid order),
    ``codes.bin`` int32 centroid ids, ``residuals.bin`` uint8 rows of
    ``dim·nbits/8`` bytes, each byte holding ``8/nbits`` codes from the
    low bits up."""

    def __init__(self, path, dim: int, nbits: int, doc_lens: np.ndarray):
        col = pathlib.Path(path) / "colbert"
        self.dim, self.nbits = dim, nbits
        self.offsets = np.zeros(len(doc_lens) + 1, np.int64)
        np.cumsum(doc_lens, out=self.offsets[1:])
        n_tok = int(self.offsets[-1])
        self.codes = np.fromfile(col / "codes.bin", np.int32)
        self.residuals = np.memmap(col / "residuals.bin", np.uint8, "r",
                                   shape=(n_tok, dim * nbits // 8))
        if self.codes.shape != (n_tok,):
            raise ValueError(f"index has {self.codes.shape[0]} tokens, "
                             f"the corpus {n_tok}")
        self.centroids = np.load(col / "centroids.npy").astype(np.float64)
        self.weights = np.load(col / "bucket_weights.npy").astype(np.float64)
        self.cutoffs = np.load(col / "bucket_cutoffs.npy").astype(np.float64)
        self.tok_pid = np.repeat(np.arange(len(doc_lens)), doc_lens)
        self.n_base = len(doc_lens)
        self.versions = None      # upserted passages (``Versions``)

    def token_rows(self, pids):
        """Token row ids of ``pids`` in order, and each passage's first
        position in them."""
        lens = self.offsets[pids + 1] - self.offsets[pids]
        first = np.zeros(len(pids), np.int64)
        np.cumsum(lens[:-1], out=first[1:])
        rows = np.repeat(self.offsets[pids] - first, lens) + np.arange(
            int(lens.sum()))
        return rows, first

    def decode(self, rows):
        """→ (embeddings, |centroid| + |residual|) float64 (n, dim)."""
        shifts = np.arange(8 // self.nbits, dtype=np.uint8) * self.nbits
        packed = np.asarray(self.residuals[rows])
        buckets = (packed[..., None] >> shifts) & ((1 << self.nbits) - 1)
        r = self.weights[buckets.reshape(len(rows), self.dim)]
        c = self.centroids[self.codes[rows]]
        return c + r, np.abs(c) + np.abs(r)


class Versions:
    """Upserted passages, encoded as the program's upsert encodes them
    (each token's nearest centroid by inner product, then each
    dimension's residual bucket by the stored cutoffs), in float64 from
    the stored centroids, cutoffs and bucket weights. Where the encoder's
    rounding could pick another centroid (its score within the two
    scores' ``ENCODE_ROUNDING`` bounds of the best) or another bucket
    (the float32 residual within its rounding of a cutoff), every choice
    is kept. Each choice is a row: token ``tok``, its decoded vector as
    ``mid ± half`` per dimension, and ``mag``, the size of centroid plus
    residual. Rows are grouped by token, tokens by passage; ``slot`` j
    holds pid ``n_base + j``, ``first[j]`` its first token."""

    def __init__(self, index: Index, embs: np.ndarray, lens: np.ndarray):
        C, W, cut = index.centroids, index.weights, index.cutoffs
        dim = C.shape[1]
        valid = np.arange(embs.shape[1])[None, :] < lens[:, None]
        e = embs[valid].astype(np.float64)                 # (N, dim)
        absC = np.abs(C)
        tok, cid = [], []
        for a in range(0, len(e), ENCODE_ROWS):
            x = e[a:a + ENCODE_ROWS]
            sc = x @ C.T
            bound = (ENCODE_ROUNDING + dim * U32) * (np.abs(x) @ absC.T)
            best = np.argmax(sc, axis=1)[:, None]
            floor = (np.take_along_axis(sc, best, 1)
                     - np.take_along_axis(bound, best, 1))
            t, c = np.nonzero(sc + bound >= floor)
            tok.append(t + a)
            cid.append(c)
        tok, cid = np.concatenate(tok), np.concatenate(cid)
        r = e[tok] - C[cid]
        b = np.searchsorted(cut, r, side="left")
        eps = 2 * U32 * (np.abs(e[tok]) + absC[cid])
        lo_b = np.where((b > 0) & (r - cut[np.maximum(b - 1, 0)] <= eps),
                        b - 1, b)
        hi_b = np.where((b < len(cut))
                        & (cut[np.minimum(b, len(cut) - 1)] - r <= eps),
                        b + 1, b)
        w_lo, w_hi = W[lo_b], W[hi_b]
        self.tok = tok
        self.mid = C[cid] + 0.5 * (w_lo + w_hi)
        self.half = 0.5 * np.abs(w_hi - w_lo)
        self.mag = absC[cid] + np.maximum(np.abs(w_lo), np.abs(w_hi))
        self.first = np.zeros(len(lens) + 1, np.int64)
        np.cumsum(lens, out=self.first[1:])
        self.row_first = np.searchsorted(tok, np.arange(len(e) + 1))
        self.choices = len(tok) - len(e)      # rows beyond one a token

    def maxsim(self, q: np.ndarray, slots: np.ndarray):
        """MaxSim of ``q`` (Lq, dim) float64 over the passages in
        ``slots`` → (scores, bounds): the middle of the range the
        choices allow, and its half-width plus the float32 bound."""
        out = np.empty(len(slots))
        tol = np.empty(len(slots))
        aq = np.abs(q)
        for i, j in enumerate(slots.tolist()):
            t0, t1 = self.first[j], self.first[j + 1]
            r0, r1 = self.row_first[t0], self.row_first[t1]
            m = self.mid[r0:r1] @ q.T
            h = self.half[r0:r1] @ aq.T
            starts = self.row_first[t0:t1] - r0
            hi = (m + h).max(0)
            lo = np.minimum.reduceat(m - h, starts, axis=0).max(0)
            big = (self.mag[r0:r1] @ aq.T).max(0)
            out[i] = 0.5 * (hi + lo).sum()
            tol[i] = (0.5 * (hi - lo).sum()
                      + 2 * (q.shape[1] + len(q)) * U32 * big.sum())
        return out, tol


def maxsim(index: Index, q: np.ndarray, pids: np.ndarray):
    """Exact MaxSim of query ``q`` (Lq, dim) over ``pids`` → (scores,
    forward-error bounds): a float32 evaluation obeys
    ``2·(dim + Lq)·2^-24·S`` with ``S`` the sum over query tokens of the
    largest absolute-product sum among the passage's tokens. Upserted
    pids are scored by ``Versions.maxsim``."""
    q = q.astype(np.float64)
    pids = np.asarray(pids)
    up = pids >= index.n_base
    if up.any():
        score, tol = np.empty(len(pids)), np.empty(len(pids))
        score[up], tol[up] = index.versions.maxsim(
            q, pids[up] - index.n_base)
        if (~up).any():
            score[~up], tol[~up] = maxsim(index, q, pids[~up])
        return score, tol
    rows, first = index.token_rows(pids)
    emb, mag = index.decode(rows)
    sim = np.maximum.reduceat(emb @ q.T, first, axis=0)
    big = np.maximum.reduceat(mag @ np.abs(q).T, first, axis=0)
    return sim.sum(1), 2 * (index.dim + len(q)) * U32 * big.sum(1)


class Splade:
    """Stage-1 scores over every passage, from the corpus's raw term
    weights quantised to the index's uint8 impacts."""

    def __init__(self, docs: dict, extra: dict | None = None):
        ids = docs["doc_term_ids"]
        w = docs["doc_term_weights"]
        self.quantum = float(w.max()) / 255.0
        if extra is not None:      # upserted passages, at the base quantum
            ids = np.concatenate([ids, extra["term_ids"]])
            w = np.concatenate([w, extra["term_weights"]])
        self.n_docs, self.nnz = ids.shape
        x = w.astype(np.float64).ravel() / self.quantum
        self.imp = np.clip(np.rint(x), 1, 255)
        # entries whose quotient sits on a rounding edge may take either
        # impact: their score can move by one quantum times the weight
        self.edge = np.abs(x - np.floor(x) - 0.5) < 1e-4
        flat = ids.ravel()
        self.order = np.argsort(flat, kind="stable")
        self.start = np.zeros(int(flat.max()) + 2, np.int64)
        np.cumsum(np.bincount(flat, minlength=len(self.start) - 1),
                  out=self.start[1:])

    def scores(self, terms, weights):
        """→ (scores (n_docs,), bounds (n_docs,)) for one query."""
        s = np.zeros(self.n_docs)
        edge = np.zeros(self.n_docs)
        m = 0
        for t, w in zip(terms.tolist(), weights.tolist()):
            if w <= 0 or t < 0 or t + 1 >= len(self.start):
                continue
            m += 1
            pos = self.order[self.start[t]:self.start[t + 1]]
            doc = pos // self.nnz
            s += np.bincount(doc, w * self.quantum * self.imp[pos],
                             self.n_docs)
            edge += np.bincount(doc, w * self.quantum * self.edge[pos],
                                self.n_docs)
        return s, (m + 3) * U32 * s + edge


def _ranked(scores, cut):
    """Indices by score descending, ties by index ascending."""
    return np.lexsort((np.arange(len(scores)), -scores))[:cut]


def _alternatives(order, s, tol, cut):
    """Admissible top-``cut`` index sets: ``order`` ranks all entries;
    an entry inside and one outside the cut may trade places where
    their scores are not equal and their error bounds overlap."""
    lo, hi = max(0, cut - BOUNDARY_WINDOW), cut + BOUNDARY_WINDOW
    inside, outside = order[lo:cut], order[cut:hi]
    if not len(outside):
        return [order[:cut]]
    can_leave, can_enter = set(), set()
    for d in inside:
        near = (s[outside] != s[d]) & (s[outside] + tol[outside]
                                       >= s[d] - tol[d])
        if near.any():
            can_leave.add(int(d))
            can_enter.update(int(e) for e in outside[near])
    alts = [order[:cut]]
    for r in range(1, min(len(can_leave), len(can_enter)) + 1):
        for out in itertools.combinations(sorted(can_leave), r):
            for inn in itertools.combinations(sorted(can_enter), r):
                keep = order[:cut][~np.isin(order[:cut], out)]
                cand = np.concatenate([keep, inn])
                alts.append(cand[_ranked(s[cand], cut)])
                if len(alts) >= MAX_ALTERNATIVES:
                    return alts
    return alts


def judge(pids, scores, ref, tol, admissible, ranked, n, k):
    """One served answer against one admissible outcome → (bad_pids,
    score_err, rank_gap). ``ref``/``tol`` map pid → reference score and
    its bound. The first ``n`` pids served must be distinct members of
    ``admissible`` and the rest of the ``k`` padding; the reference
    score at rank j is held against the j-th best of ``ranked`` and the
    pids served."""
    got = [int(x) for x in pids[:n]]
    bad = int(np.sum(np.asarray(pids[n:k]) >= 0))
    ok, seen = [], set()
    for j, x in enumerate(got):
        if x not in admissible or x in seen:
            bad += 1
        else:
            ok.append(j)
        seen.add(x)
    if not ok:
        return bad, np.inf, np.inf
    ok = np.asarray(ok)
    r = np.array([ref[got[j]] for j in ok])
    t = np.array([tol[got[j]] for j in ok])
    err = float(np.max(np.abs(np.asarray(scores, np.float64)[ok] - r) / t))
    pop = set(int(x) for x in ranked) | {got[j] for j in ok}
    best = np.sort([ref[x] for x in pop])[::-1]
    unit = max(tol[x] for x in pop)
    inside = ok < len(best)
    gap = float(np.max(np.maximum(best[ok[inside]] - r[inside], 0.0),
                       initial=0.0) / unit)
    return bad, err, gap


def _best(results):
    return min(results, key=lambda x: (x[0], max(x[1], x[2])))


def hybrid_outcomes(index, splade, q_emb, terms, weights, p,
                    states=(None,)):
    """Every admissible outcome of one hybrid query, over the write
    ``states`` (masks of the pids alive in each; None: the corpus as
    built) and each state's admissible candidate lists → [(candidates,
    {pid: fused score}, {pid: bound})]."""
    s0, ts = splade.scores(terms, weights)
    c_of, tc_of = {}, {}
    a, u = p["alpha"], U32
    out = []
    for alive in states:
        s = s0 if alive is None else np.where(alive, s0, -np.inf)
        order = _ranked(s, p["first_k"] + BOUNDARY_WINDOW)
        alts = _alternatives(order, s, ts, p["first_k"])
        pool = [x for x in np.unique(np.concatenate(alts)).tolist()
                if x not in c_of]
        if pool:
            c_pool, tc_pool = maxsim(index, q_emb, np.array(pool))
            c_of.update(zip(pool, c_pool))
            tc_of.update(zip(pool, tc_pool))
        for cand in alts:
            sv = s[cand]
            cv = np.array([c_of[int(x)] for x in cand])
            tcv = np.array([tc_of[int(x)] for x in cand])
            n = len(cand)
            fused, tol = 0.0, 0.0
            for x, w, t in ((sv, a, ts[cand]), (cv, 1 - a, tcv)):
                mean, std = x.mean(), max(x.std(), 1e-9)
                fused = fused + w * (x - mean) / std
                # the input's own error through the normaliser, plus the
                # float32 rounding of a length-n mean and variance
                tol += w * (2 * t.max() / std + (n + 8) * u
                            * (np.abs(x).max() + abs(mean)) / std)
            out.append((cand, dict(zip(cand.tolist(), fused)),
                        dict.fromkeys(cand.tolist(), tol)))
    return out


def check_hybrid(index, splade, q_emb, terms, weights, pids, scores, p,
                 states=(None,)):
    out = [judge(pids, scores, ref, tols, set(ref), cand,
                 min(p["k"], len(cand)), p["k"])
           for cand, ref, tols in hybrid_outcomes(
               index, splade, q_emb, terms, weights, p, states)]
    return _best(out), len(out)


def check_plaid(index, q_emb, pids, scores, p):
    q = q_emb.astype(np.float64)
    K, dim = index.centroids.shape
    sc = q @ index.centroids.T                          # (Lq, K)
    tc = 2 * dim * U32 * (np.abs(q) @ np.abs(index.centroids).T)
    npb = p["nprobe"]
    top = np.argpartition(-sc, npb + 16, axis=1)[:, :npb + 16]
    hi_, lo_ = sc + tc, sc - tc
    certain = np.zeros(K, bool)
    possible = np.zeros(K, bool)
    for i in range(len(q)):
        row = top[i]
        for c in row:
            others = row[row != c]
            if np.sum(hi_[i, others] >= lo_[i, c]) < npb:
                certain[c] = True
            if np.sum(lo_[i, others] > hi_[i, c]) < npb:
                possible[c] = True
    c_cert = np.unique(index.tok_pid[certain[index.codes]])
    c_poss = np.unique(index.tok_pid[possible[index.codes]])
    rows, first = index.token_rows(c_poss)
    best = np.maximum.reduceat(np.ascontiguousarray(sc.T)[index.codes[rows]],
                               first, axis=0)           # (C, Lq)
    approx = best.sum(1)
    # each query token's best centroid score carries at most that
    # token's largest centroid-score bound, and the sum its rounding
    atol = tc.max(1).sum() + len(q) * U32 * np.abs(best).sum(1)
    is_cert = np.isin(c_poss, c_cert)
    nd = harness.survivors(p)
    hi_a, lo_a = approx + atol, approx - atol
    # a certain survivor: a certain candidate that fewer than ndocs
    # others could reach; a possible one: fewer than ndocs certain
    # candidates surely beat it
    hi_sorted = np.sort(hi_a)
    reach = len(hi_sorted) - np.searchsorted(hi_sorted, lo_a) - 1
    lo_cert = np.sort(lo_a[is_cert])
    beat = len(lo_cert) - np.searchsorted(lo_cert, hi_a, side="right")
    surv_cert = c_poss[is_cert & (reach < nd)]
    surv_poss = c_poss[beat < nd]
    ex, tex = maxsim(index, q_emb, surv_poss)
    ref = dict(zip(surv_poss.tolist(), ex))
    tols = dict(zip(surv_poss.tolist(), tex))
    n = min(p["k"], len(surv_poss))
    return judge(pids, scores, ref, tols, set(ref), surv_cert, n,
                 p["k"]), int(len(surv_poss) > len(surv_cert))


def n_slots(client: dict, n_base: int) -> int:
    """Upserted pids run from ``n_base`` to the largest one assigned."""
    ok = (client["w_status"] == stats.OK) & (client["w_op"] == 0)
    return int(client["w_pid"][ok].max(initial=n_base - 1) - n_base + 1)


class WriteLog:
    """The writes a run sent, in order, and the corpus after each prefix
    of them: ``alive[m]`` marks the pids alive once the first m writes
    are applied (base pids, then one slot per upserted pid). A write
    that failed changes nothing."""

    def __init__(self, client: dict, n_base: int):
        ok = client["w_status"] == stats.OK
        sent = ~np.isnan(client["w_sent"])
        self.sent = np.where(sent, client["w_sent"], np.inf)
        self.ack = np.where(ok, client["w_ack"], np.inf)
        alive = np.zeros(n_base + n_slots(client, n_base), bool)
        alive[:n_base] = True
        self.alive = [alive.copy()]
        for op, pid, good in zip(client["w_op"].tolist(),
                                 client["w_pid"].tolist(), ok.tolist()):
            if good:
                alive[pid] = op == 0
            self.alive.append(alive.copy())

    def states(self, sent: float, done: float) -> list:
        """The corpora a query sent at ``sent`` and answered at ``done``
        may have seen: every write acknowledged before the send applied,
        none sent after the reply."""
        lo = int(np.sum(self.ack < sent))
        hi = max(lo, int(np.sum(self.sent <= done)))
        return self.alive[lo:hi + 1]


def upserted(cfg: dict, traffic: dict, client: dict, docs: dict,
             seed: int, n_base: int) -> dict:
    """The passage behind each upserted pid, drawn again from the seed:
    {term_ids, term_weights, embs, lens} by slot (pid − n_base)."""
    corpus = harness.corpus(cfg)
    m = int(client["w_version"].max()) + 1
    keys = gen.write_keys(corpus, traffic["writes"], m, seed)
    v = gen.make_versions(corpus, docs, keys, seed)
    ups = (client["w_op"] == 0) & (client["w_version"] >= 0)
    if not np.array_equal(keys[client["w_version"][ups]],
                          client["w_key"][ups]):
        raise ValueError("the write log's keys are not the seed's")
    ok = ups & (client["w_status"] == stats.OK)
    which = np.zeros(n_slots(client, n_base), np.int64)  # empty: never alive
    which[client["w_pid"][ok] - n_base] = client["w_version"][ok]
    return {k: v[k][which] for k in ("term_ids", "term_weights", "embs",
                                     "lens")}


def check(cfg: dict, traffic: dict, index_dir, seed: int, client: dict,
          sample: np.ndarray, forget_writes: bool = False) -> dict:
    """Judge the sampled answers → {name: value} for ``failed``,
    ``bad_pids``, ``score_err``, ``rank_gap``; ``ambiguous`` (sampled
    answers with more than one admissible outcome) and ``states``
    (sampled answers with more than one admissible write state) are
    information, not compared. ``forget_writes`` judges against the
    corpus as built, as though no write had been made: the control of
    the check under writes."""
    corpus, serving = harness.corpus(cfg), cfg["serving"]
    p = dict(serving, k=traffic["k"])
    docs = gen.make_corpus(corpus, seed)
    n = len(client["status"])
    queries = gen.make_queries(corpus, docs, n, seed,
                               gen.query_rel(corpus, traffic, n, seed))
    index = Index(index_dir, corpus["dim"], cfg["index"]["nbits"],
                  docs["doc_lens"])
    log, extra = None, None
    if "w_status" in client and not forget_writes:
        if serving["method"] != "hybrid":
            raise ValueError("writes are judged under hybrid serving only")
        log = WriteLog(client, index.n_base)
        extra = upserted(cfg, traffic, client, docs, seed, index.n_base)
        index.versions = Versions(index, extra["embs"], extra["lens"])
    splade = (Splade(docs, extra) if serving["method"] == "hybrid"
              else None)
    failed = stats.failed(client)

    def one(i):
        pids, scores = client["pids"][i], client["scores"][i]
        if splade is not None:
            states = ((None,) if log is None else log.states(
                client["sent"][i], client["done"][i]))
            return check_hybrid(
                index, splade, queries["q_embs"][i],
                queries["q_term_ids"][i], queries["q_term_weights"][i],
                pids, scores, p, states), len(states)
        return check_plaid(index, queries["q_embs"][i], pids, scores,
                           p), 1
    answered = [i for i in sample.tolist() if client["status"][i] == 0]
    with ThreadPoolExecutor(THREADS) as pool:
        res = list(pool.map(one, answered))
    out = [r for r, _ in res]
    bad = sum(b for (b, _, _), _ in out)
    err = max((e for (_, e, _), _ in out), default=0.0)
    gap = max((g for (_, _, g), _ in out), default=0.0)
    amb = sum(n_alt > 1 for _, n_alt in out)
    return {"failed": failed, "bad_pids": bad, "score_err": err,
            "rank_gap": gap, "ambiguous": amb,
            "states": sum(n_states > 1 for _, n_states in res)}
