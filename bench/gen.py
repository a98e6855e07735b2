"""Data of one run, drawn from ``--seed``: corpus, queries, arrivals.

NumPy only, vectorised, no per-passage Python loop. Every seed draws the
same sizes: the multiset of passage lengths is a fixed set of quantiles
of the configuration's length distribution, and the multiset of
inter-arrival gaps a fixed set of exponential quantiles; the seed only
orders them and draws the vectors, terms and weights. So the token
count, and with it every compiled shape, is the same for every seed.

The model follows the topic model of the program's ``data/synth.py``
(documents near a topic vector, queries near their relevant document,
a lexical view drawn from topic vocabularies), at MS MARCO's shapes:

* term popularity is Zipfian over the whole vocabulary; each topic owns
  a vocabulary drawn from it, and a passage's SPLADE terms are distinct
  draws from its topic's vocabulary;
* each passage token takes the identity of one of the passage's terms,
  so token embeddings cluster by term as ColBERT's do, and the IVF
  lists of frequent terms are long;
* a query's terms are distinct draws from its relevant passage's terms,
  a share of them swapped for other terms of the topic (the lexical
  gap); its 32 token vectors lie near the passage's terms and vector.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SLAB_DOCS = 2048
THREADS = 8        # NumPy's generators and large array passes drop the GIL


def seed_word(seed: int) -> int:
    """Any whole number as an unsigned 64-bit seed word."""
    return int(seed) % (1 << 64)


def _unit(x, axis=-1):
    n = np.linalg.norm(x, axis=axis, keepdims=True)
    return x / np.maximum(n, np.float32(1e-9))


def _quantile_lengths(n, mean, sigma, lo, hi):
    """n passage lengths: quantiles of a lognormal clipped to [lo, hi],
    with ``mu`` set so that their mean is ``mean``."""
    z = _normal_quantiles(n)

    def lengths(mu):
        return np.clip(np.rint(np.exp(mu + sigma * z)), lo, hi)

    a, b = np.log(lo), np.log(hi)
    for _ in range(60):
        mid = 0.5 * (a + b)
        if lengths(mid).mean() < mean:
            a = mid
        else:
            b = mid
    return lengths(0.5 * (a + b)).astype(np.int32)


def _normal_quantiles(n):
    """Standard normal quantiles at (i + 0.5) / n (Acklam's rational
    approximation, |error| < 1.2e-9: SciPy is not needed)."""
    p = (np.arange(n) + 0.5) / n
    a = [-3.969683028665376e+01, 2.209460984245205e+02,
         -2.759285104469687e+02, 1.383577518672690e+02,
         -3.066479806614716e+01, 2.506628277459239e+00]
    b = [-5.447609879822406e+01, 1.615858368580409e+02,
         -1.556989798598866e+02, 6.680131188771972e+01,
         -1.328068155288572e+01]
    c = [-7.784894002430293e-03, -3.223964580411365e-01,
         -2.400758277161838e+00, -2.549732539343734e+00,
         4.374664141464968e+00, 2.938163982698783e+00]
    d = [7.784695709041462e-03, 3.224671290700398e-01,
         2.445134137142996e+00, 3.754408661907416e+00]
    out = np.empty(n)
    lo, hi = p < 0.02425, p > 1 - 0.02425
    mid = ~(lo | hi)
    q = p[mid] - 0.5
    r = q * q
    out[mid] = ((((((a[0] * r + a[1]) * r + a[2]) * r + a[3]) * r + a[4])
                 * r + a[5]) * q) / (((((b[0] * r + b[1]) * r + b[2]) * r
                                       + b[3]) * r + b[4]) * r + 1)
    for m, sign, pp in ((lo, 1.0, p[lo]), (hi, -1.0, 1 - p[hi])):
        q = np.sqrt(-2 * np.log(pp))
        out[m] = sign * (((((c[0] * q + c[1]) * q + c[2]) * q + c[3]) * q
                          + c[4]) * q + c[5]) / ((((d[0] * q + d[1]) * q
                                                   + d[2]) * q + d[3]) * q
                                                 + 1)
    return out


def _gumbel_topk(rng, logp, k):
    """Per row, k distinct column indices drawn without replacement with
    probabilities ∝ exp(logp) (Gumbel top-k)."""
    u = np.maximum(rng.random(logp.shape, dtype=np.float32),
                   np.float32(1e-30))
    g = logp - np.log(-np.log(u))
    return np.argpartition(-g, k - 1, axis=-1)[..., :k]


def doc_lengths(corpus: dict) -> np.ndarray:
    """The fixed multiset of passage lengths (not yet ordered)."""
    return _quantile_lengths(corpus["n_docs"], corpus["avg_doclen"],
                             corpus["doclen_sigma"], corpus["doc_minlen"],
                             corpus["doc_maxlen"])


def n_tokens(corpus: dict) -> int:
    return int(doc_lengths(corpus).sum())


def _streams(seed: int):
    """Independent seed sequences for the corpus, its token vectors and
    the queries, so that each can be drawn without the others."""
    return np.random.SeedSequence([seed_word(seed), 0x5EED]).spawn(3)


def _slabs(n: int, seq: np.random.SeedSequence, fn):
    """Run ``fn(lo, hi, rng)`` over slabs of ``SLAB_DOCS`` passages on a
    few threads, each slab with its own generator: the draws do not
    depend on how the threads interleave."""
    los = list(range(0, n, SLAB_DOCS))
    rngs = [np.random.default_rng(s) for s in seq.spawn(len(los))]
    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(lambda a: fn(a[0], min(a[0] + SLAB_DOCS, n), a[1]),
                      zip(los, rngs)))


def make_corpus(corpus: dict, seed: int) -> dict:
    """Everything of the corpus but its token vectors: passage lengths,
    topics and SPLADE terms (n_docs, doc_nnz) with their weights, and
    the term, topic and passage vectors the tokens and queries are
    drawn around. A few seconds at MS MARCO's shapes."""
    c = corpus
    seq = _streams(seed)[0]
    rng = np.random.default_rng(seq)
    n_docs, dim, V = c["n_docs"], c["dim"], c["vocab"]
    T, tv, nnz = c["n_topics"], c["topic_vocab"], c["doc_nnz"]

    lens = rng.permutation(doc_lengths(c))
    log_pop = -c["zipf_s"] * np.log(np.arange(1, V + 1, dtype=np.float32))
    topic_terms = np.sort(_gumbel_topk(rng, np.broadcast_to(
        log_pop, (T, V)), tv), axis=1).astype(np.int32)   # (T, tv)
    topic_logp = c["topic_zipf_s"] / c["zipf_s"] * log_pop[topic_terms]
    type_vec = _unit(rng.standard_normal((V, dim), dtype=np.float32))
    topic_vec = _unit(rng.standard_normal((T, dim), dtype=np.float32))

    doc_topic = rng.integers(0, T, n_docs)
    doc_vec = _unit(topic_vec[doc_topic] + np.float32(c["doc_sig"]) * _unit(
        rng.standard_normal((n_docs, dim), dtype=np.float32)))
    doc_term_ids = np.empty((n_docs, nnz), np.int32)

    def terms(lo, hi, r):
        slot = _gumbel_topk(r, topic_logp[doc_topic[lo:hi]], nnz)
        doc_term_ids[lo:hi] = np.take_along_axis(
            topic_terms[doc_topic[lo:hi]], slot, axis=1)
    _slabs(n_docs, seq, terms)
    doc_term_w = (np.float32(c["weight_floor"]) + rng.gamma(
        2.0, c["weight_scale"], (n_docs, nnz)).astype(np.float32))
    return {"doc_lens": lens.astype(np.int32), "doc_topic": doc_topic,
            "doc_term_ids": doc_term_ids, "doc_term_weights": doc_term_w,
            "topic_terms": topic_terms, "type_vec": type_vec,
            "doc_vec": doc_vec, "topic_vec": topic_vec,
            "topic_logp": topic_logp}


def make_doc_embs(corpus: dict, docs: dict, seed: int) -> np.ndarray:
    """Token vectors in the padded layout the index builder takes:
    (n_docs, doc_maxlen, dim) float32, zero past each passage's length.
    Each token is one of its passage's terms."""
    c = corpus
    lens, dim, nnz = docs["doc_lens"], c["dim"], c["doc_nnz"]
    out = np.zeros((len(lens), c["doc_maxlen"], dim), np.float32)
    valid = np.arange(c["doc_maxlen"])[None, :] < lens[:, None]
    a, b, s = (np.float32(c[k]) for k in ("tok_type", "tok_doc",
                                          "tok_noise"))

    def tokens(lo, hi, rng):
        doc = np.repeat(np.arange(lo, hi), lens[lo:hi])
        typ = docs["doc_term_ids"][doc, rng.integers(0, nnz, len(doc))]
        x = a * docs["type_vec"][typ]
        x += b * docs["doc_vec"][doc]
        x += s * rng.standard_normal((len(doc), dim), dtype=np.float32)
        out[lo:hi][valid[lo:hi]] = _unit(x)
    _slabs(len(lens), _streams(seed)[1], tokens)
    return out


def make_queries(corpus: dict, docs: dict, n: int, seed: int,
                 rel: np.ndarray | None = None) -> dict:
    """n queries, each with a relevant passage: distinct terms drawn from
    its terms by weight, a share swapped for other terms of its topic,
    and query_maxlen token vectors near those terms and the passage.
    The relevant passages are uniform draws unless ``rel`` names them
    (``query_rel``). → q_embs (n, query_maxlen, dim), q_term_ids /
    q_term_weights (n, query_nnz), q_rel (n,)."""
    c = corpus
    rng = np.random.default_rng(_streams(seed)[2])
    qn, lq, tv = c["query_nnz"], c["query_maxlen"], c["topic_vocab"]
    # drawn either way, so that the draws after it stay where they were
    drawn = rng.integers(0, c["n_docs"], n)
    rel = drawn if rel is None else np.asarray(rel)
    w_rel = docs["doc_term_weights"][rel]
    pick = _gumbel_topk(rng, np.log(w_rel), qn)
    terms = np.take_along_axis(docs["doc_term_ids"][rel], pick, axis=1)
    w = (np.take_along_axis(w_rel, pick, axis=1)
         * rng.uniform(0.5, 1.5, (n, qn)).astype(np.float32))
    gap = rng.random((n, qn)) < c["lex_gap"]
    swap = docs["topic_terms"][docs["doc_topic"][rel][:, None],
                               rng.integers(0, tv, (n, qn))]
    terms = np.where(gap, swap, terms).astype(np.int32)
    tok_type = np.take_along_axis(terms, rng.integers(0, qn, (n, lq)),
                                  axis=1)
    a, b, s = (np.float32(c[k]) for k in ("q_type", "q_doc", "q_noise"))
    q = a * docs["type_vec"][tok_type] + b * docs["doc_vec"][rel][:, None, :]
    q += s * rng.standard_normal((n, lq, c["dim"]), dtype=np.float32)
    return {"q_embs": _unit(q).astype(np.float32), "q_term_ids": terms,
            "q_term_weights": w.astype(np.float32),
            "q_rel": rel.astype(np.int64)}


def arrivals(n: int, seconds: float, seed: int) -> np.ndarray:
    """Due times (s from the window's start) of ``n`` open-loop arrivals:
    the gaps, the first one from the window's start, are the n
    exponential quantiles at (i + 0.5) / n in an order drawn from the
    seed, scaled so that the last arrival falls inside the window."""
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = np.random.default_rng([seed_word(seed), 0xA221]).permutation(gaps)
    return np.cumsum(gaps) * (seconds * (1 - 0.5 / n) / gaps.sum())


# Read/write traffic. Each draw below has a seed stream of its own, so
# that the corpus, the ``unique`` queries and the arrivals are the same
# with or without it.

def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([seed_word(seed), stream])


def popularity(n_docs: int, seed: int) -> np.ndarray:
    """The passages ranked by popularity: one seeded permutation of the
    pids, most popular first."""
    return _rng(seed, 0x9091).permutation(n_docs)


def zipf_ranks(rng: np.random.Generator, n_items: int, s: float,
               size: int) -> np.ndarray:
    """``size`` ranks in [0, n_items), rank r drawn with probability
    proportional to (r + 1)^-s, as YCSB's Zipfian generator draws."""
    cdf = np.cumsum(np.arange(1, n_items + 1, dtype=np.float64) ** -s)
    u = rng.random(size) * cdf[-1]
    return np.minimum(np.searchsorted(cdf, u, side="right"), n_items - 1)


def query_rel(corpus: dict, traffic: dict, n: int, seed: int):
    """The relevant passages of ``n`` queries: None for ``unique``
    queries (``make_queries`` draws them uniformly), Zipfian over the
    popularity ranking at ``zipf_s`` for ``zipf``."""
    kind = traffic.get("queries", "unique")
    if kind == "unique":
        return None
    if kind != "zipf":
        raise ValueError(f"unknown queries {kind!r}")
    ranks = zipf_ranks(_rng(seed, 0x2197), corpus["n_docs"],
                       traffic["zipf_s"], n)
    return popularity(corpus["n_docs"], seed)[ranks]


def write_keys(corpus: dict, writes: dict, n: int, seed: int) -> np.ndarray:
    """The keys (original pids) of ``n`` updates, Zipfian over the same
    popularity ranking at ``key_zipf_s``: the set-up's preload first,
    then the window's."""
    ranks = zipf_ranks(_rng(seed, 0x3171), corpus["n_docs"],
                       writes["key_zipf_s"], n)
    return popularity(corpus["n_docs"], seed)[ranks]


def write_slots(n_arrivals: int, share: float, seed: int) -> np.ndarray:
    """Which of the window's arrivals are writes: ``round(share · n)`` of
    them, chosen from the seed, so every seed writes as often."""
    n_w = int(round(share * n_arrivals))
    mask = np.zeros(n_arrivals, bool)
    mask[_rng(seed, 0x3172).permutation(n_arrivals)[:n_w]] = True
    return mask


def make_versions(corpus: dict, docs: dict, keys: np.ndarray,
                  seed: int) -> dict:
    """A new version of each key's passage, one per update in order: the
    key's topic and length, a new passage vector around the topic's, new
    SPLADE terms and weights from the topic's vocabulary, and new token
    vectors, drawn as ``make_corpus`` and ``make_doc_embs`` draw them.
    → lens (m,), embs (m, doc_maxlen, dim) zero past each length,
    term_ids / term_weights (m, doc_nnz)."""
    c = corpus
    rng = _rng(seed, 0x7E25)
    keys = np.asarray(keys, np.int64)
    m, dim, nnz = len(keys), c["dim"], c["doc_nnz"]
    topic = docs["doc_topic"][keys]
    lens = docs["doc_lens"][keys].astype(np.int32)
    vec = _unit(docs["topic_vec"][topic] + np.float32(c["doc_sig"]) * _unit(
        rng.standard_normal((m, dim), dtype=np.float32)))
    slot = _gumbel_topk(rng, docs["topic_logp"][topic], nnz)
    term_ids = np.take_along_axis(docs["topic_terms"][topic], slot,
                                  axis=1).astype(np.int32)
    term_w = (np.float32(c["weight_floor"]) + rng.gamma(
        2.0, c["weight_scale"], (m, nnz)).astype(np.float32))
    embs = np.zeros((m, c["doc_maxlen"], dim), np.float32)
    valid = np.arange(c["doc_maxlen"])[None, :] < lens[:, None]
    doc = np.repeat(np.arange(m), lens)
    typ = term_ids[doc, rng.integers(0, nnz, len(doc))]
    a, b, s = (np.float32(c[k]) for k in ("tok_type", "tok_doc",
                                          "tok_noise"))
    x = a * docs["type_vec"][typ] + b * vec[doc]
    x += s * rng.standard_normal((len(doc), dim), dtype=np.float32)
    embs[valid] = _unit(x)
    return {"lens": lens, "embs": embs, "term_ids": term_ids,
            "term_weights": term_w.astype(np.float32)}
