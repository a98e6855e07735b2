#!/usr/bin/env python3
"""Chip smoke: serve the multi-stage path once on a TPU, end to end.

    python chip_smoke.py [--seed 0] [--n-docs 65536]    # one chip
    python chip_smoke.py --chips 4                      # 4-shard group

One process does everything, because a chip belongs to the process that
first touches it: it builds a synthetic corpus at published widths
(128-d tokens, 32-token queries, 180-token documents, a 30,522-term
vocabulary), builds the ColBERT index (4-bit residuals, 2^15 centroids
at this token count) and the SPLADE index on disk, opens them through
``launch.serve.build_or_load`` in mmap mode, and serves them through
``ServeEngine``, ``RetrievalServer`` and the TCP front.

One chip: 32 requests per method over TCP at ``max_batch`` 8 (all four
methods at pipeline depth 1, ``hybrid`` again at depth 2 and with the
Pallas SPLADE stage 1). ``rerank`` and ``hybrid`` answers are checked
against a NumPy float64 reference on the same stage-1 candidates, and
the compiled tail programs must contain the Pallas kernel
(``tpu_custom_call``). ``--chips 4`` instead serves a 4-shard thread
group, one shard per chip, and checks it against the 1-shard answers
and the same reference.

Tolerance: the served path computes every float32 dot at
``Precision.HIGHEST``, so a served score ``s`` of an exact score ``x``
obeys the forward-error bound of a float32 evaluation,
``|s - x| <= 2·(d + Lq)·2^-24·S`` with ``S`` the sum of the absolute
products that make up ``x``; a single bf16 pass would miss it by orders
of magnitude. Near-ties within the tolerance may swap ranks.

Exits non-zero on any failed phase, or when JAX finds no TPU. The last
line of standard output is the JSON result; everything else comes
before it.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import tempfile
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

DIM, Q_LEN, DOC_MAXLEN, VOCAB = 128, 32, 180, 30_522
MIN_DOCS = 32_768
N_REQUESTS, MAX_BATCH, K = 32, 8, 100
U32 = 2.0 ** -24                      # float32 unit roundoff
METHODS = ("colbert", "splade", "rerank", "hybrid")


def log(*args):
    print(*args, flush=True)


def check_device(chips: int):
    devs = jax.devices()
    d0 = devs[0]
    log(f"jax {jax.__version__}; devices: {devs}")
    log(f"platform={d0.platform} device_kind={d0.device_kind} "
        f"count={len(devs)}")
    if d0.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{d0.platform!r}")
    if len(devs) < chips:
        sys.exit(f"chip_smoke: --chips {chips} but JAX sees {len(devs)}")
    return d0


def dot_precision():
    """Relative error of one float32 matmul at the default and at the
    highest precision, in float32 ulps (2^-24) of Σ|a·b|: shows which
    precision the chip uses for float32 dots."""
    rng = np.random.default_rng(0)
    a = rng.standard_normal((256, 128)).astype(np.float32)
    b = rng.standard_normal((128, 256)).astype(np.float32)
    exact = a.astype(np.float64) @ b.astype(np.float64)
    scale = np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64)
    err = {}
    for name, prec in (("default", None),
                       ("highest", jax.lax.Precision.HIGHEST)):
        got = jax.jit(lambda x, y, p=prec: jnp.dot(x, y, precision=p))(a, b)
        err[name] = float(np.max(np.abs(np.asarray(got, np.float64) - exact)
                                 / scale) / U32)
    log(f"float32 dot error in 2^-24 units of sum|a*b|: "
        f"default={err['default']:.1f} highest={err['highest']:.1f}")
    if err["highest"] > 2 * a.shape[1]:
        raise AssertionError("Precision.HIGHEST is not full float32 here")
    return err


# ---------------------------------------------------------------------------
# corpus + index
# ---------------------------------------------------------------------------

def build_indexes(base: pathlib.Path, n_docs: int, seed: int):
    from repro.data.synth import SynthCfg, make_corpus
    from repro.index.builder import build_colbert_index
    from repro.index.splade_index import build_splade_index

    cfg = SynthCfg(n_docs=n_docs, n_queries=N_REQUESTS, vocab=VOCAB,
                   dim=DIM, doc_maxlen=DOC_MAXLEN, query_maxlen=Q_LEN,
                   seed=seed)
    t0 = time.perf_counter()
    corpus = make_corpus(cfg)
    log(f"corpus: {n_docs} passages, {int(corpus['doc_lens'].sum())} "
        f"tokens ({time.perf_counter() - t0:.1f} s)")
    t0 = time.perf_counter()
    build_colbert_index(base / "colbert", corpus["doc_embs"],
                        corpus["doc_lens"], nbits=4, seed=seed)
    build_splade_index(corpus["doc_term_ids"], corpus["doc_term_weights"],
                       cfg.vocab, cfg.n_docs).save(base / "splade")
    log(f"index: built in {time.perf_counter() - t0:.1f} s")
    return {"q_embs": corpus["q_embs"], "term_ids": corpus["q_term_ids"],
            "term_weights": corpus["q_term_weights"]}


# ---------------------------------------------------------------------------
# serving over TCP
# ---------------------------------------------------------------------------

def warm(retr, queries):
    """Compile every batch shape the server can form before serving, so
    no request waits on a compile."""
    for method in METHODS:
        for b in (1, 2, 4, 8):
            retr.search_batch(method, q_embs=list(queries["q_embs"][:b]),
                              term_ids=list(queries["term_ids"][:b]),
                              term_weights=list(queries["term_weights"][:b]),
                              k=K)


def serve(retr, queries, methods, depth: int):
    """Serve ``N_REQUESTS`` concurrent TCP requests per method through
    ServeEngine + RetrievalServer; → ({method: [(pids, scores)]},
    health)."""
    from repro.serving.engine import ServeEngine
    from repro.serving.server import RetrievalServer, tcp_query

    retr.reset_stage_stats()
    engine = ServeEngine(retr, pipeline_depth=depth)
    server = RetrievalServer(engine, max_batch=MAX_BATCH,
                             batch_timeout_ms=20.0)
    server.start()
    tcp = server.serve_tcp("127.0.0.1", 0)
    loop = threading.Thread(target=tcp.serve_forever, daemon=True)
    loop.start()
    out = {}
    try:
        for method in methods:
            def ask(i, method=method):
                return tcp_query("127.0.0.1", server.tcp_port, {
                    "qid": i, "method": method, "k": K,
                    "q_emb": queries["q_embs"][i].tolist(),
                    "term_ids": queries["term_ids"][i].tolist(),
                    "term_weights": queries["term_weights"][i].tolist()})
            t0 = time.perf_counter()
            with ThreadPoolExecutor(N_REQUESTS) as pool:
                replies = list(pool.map(ask, range(N_REQUESTS)))
            wall = time.perf_counter() - t0
            bad = [r for r in replies if "error" in r]
            if bad:
                raise AssertionError(f"{method}: {len(bad)} failed "
                                     f"requests, first: {bad[0]}")
            out[method] = [(np.asarray(r["pids"], np.int64),
                            np.asarray(r["scores"], np.float64))
                           for r in sorted(replies, key=lambda r: r["qid"])]
            log(f"served {method} depth={depth} "
                f"stage1={retr.splade_backend}: {len(replies)} requests "
                f"over TCP in {wall:.2f} s")
        health = server.health()
    finally:
        server.shutdown_gracefully()
        tcp.server_close()
        loop.join(timeout=10)
        engine.close()
    return out, health


def log_stages(health):
    for name, r in sorted(health.get("stages", {}).items()):
        log(f"  stage {name:<24} wall={r['wall_s']:.4f} s "
            f"dispatches={r['dispatches']}")


# ---------------------------------------------------------------------------
# float64 reference
# ---------------------------------------------------------------------------

def maxsim64(index, q, cand):
    """Exact MaxSim of query ``q`` (Lq, d) over candidate pids (−1 pad),
    decoded with the index's own codec arrays in float64 → (scores (C,)
    −inf at padding, tolerance (C,)): the float32 forward-error bound
    ``2·(d + Lq)·2^-24·S``, ``S`` the absolute-product sum of the
    centroid and residual terms of each maximum."""
    codes, packed, valid = index.gather_doc_tokens(cand)
    nbits = index.nbits
    shifts = (np.arange(8 // nbits) * nbits).astype(np.uint8)
    res = (packed[..., None] >> shifts) & np.uint8((1 << nbits) - 1)
    res = res.reshape(*packed.shape[:-1], -1)
    c = index.centroids.astype(np.float64)[codes]
    r = index.bucket_weights.astype(np.float64)[res]
    q64 = q.astype(np.float64)
    sim = np.einsum("qd,cld->cql", q64, c + r)
    mag = np.einsum("qd,cld->cql", np.abs(q64), np.abs(c) + np.abs(r))
    tok = valid[:, None, :]
    per_q = np.where(tok, sim, -np.inf).max(-1)
    score = np.where(np.isfinite(per_q), per_q, 0.0).sum(-1)
    bound = np.where(tok, mag, 0.0).max(-1).sum(-1)
    real = cand >= 0
    tol = 2 * (q.shape[1] + q.shape[0]) * U32 * bound
    return np.where(real, score, -np.inf), np.where(real, tol, 0.0)


def znorm64(x, mask):
    m = mask.astype(np.float64)
    n = max(m.sum(), 1.0)
    mean = (x * m).sum() / n
    std = np.sqrt((np.square(x - mean) * m).sum() / n)
    return (x - mean) / max(std, 1e-9), mean, std


def reference(retr, index, queries, method: str, backend: str):
    """Per-query (candidate pids, float64 final scores, tolerance) for
    the served ``rerank``/``hybrid`` answers on the stage-1 candidates
    the served path sees under ``backend``."""
    p = retr.params
    tids, tws = list(queries["term_ids"]), list(queries["term_weights"])
    pids_b, s_b = retr.run_splade_batch(tids, tws, k=p.first_k,
                                        backend=backend, _record=False)
    refs = []
    for i in range(len(tids)):
        cand = pids_b[i]
        c, tol_c = maxsim64(index, queries["q_embs"][i], cand)
        mask = cand >= 0
        if method == "rerank":
            refs.append((cand, c, tol_c))
            continue
        s = np.where(mask, s_b[i].astype(np.float64), 0.0)
        zs, ms, ss = znorm64(s, mask)
        zc, mc, sc = znorm64(np.where(mask, c, 0.0), mask)
        a = p.alpha
        final = np.where(mask, a * zs + (1 - a) * zc, -np.inf)
        # the colbert error through z-norm, plus float32 rounding of the
        # two z-norms (a length-n float32 sum: n·2^-24 relative)
        n = int(mask.sum())

        def zround(x, mean, std):
            return (n + 8) * U32 * (np.abs(x[mask]).max() + abs(mean)) / \
                max(std, 1e-9)
        tol = ((1 - a) * (2 * tol_c.max() / max(sc, 1e-9)
                          + zround(c, mc, sc)) + a * zround(s, ms, ss))
        refs.append((cand, final, np.where(mask, tol, 0.0)))
    return refs


def check(name, answers, refs):
    """Served (pids, scores) per query against the reference: every
    score within its tolerance, every pid a candidate, and the order the
    reference order except swaps between candidates whose reference
    scores lie within the (doubled) tolerance of each other."""
    worst = 0.0
    for qi, ((pids, scores), (cand, ref, tol)) in enumerate(
            zip(answers, refs)):
        order = np.argsort(-ref, kind="stable")
        n = min(len(pids), int(np.isfinite(ref).sum()))
        got = pids[:n]
        if np.any(got < 0) or len(set(got.tolist())) != n:
            raise AssertionError(f"{name} q{qi}: pids {got} not {n} "
                                 f"distinct candidates")
        pos = {int(p): j for j, p in enumerate(cand) if p >= 0}
        t = float(tol.max())
        for j, pid in enumerate(got):
            c = pos.get(int(pid))
            if c is None:
                raise AssertionError(f"{name} q{qi}: pid {pid} is not a "
                                     f"stage-1 candidate")
            err = abs(scores[j] - ref[c])
            worst = max(worst, err / max(tol[c], 1e-30))
            if err > tol[c]:
                raise AssertionError(
                    f"{name} q{qi} rank {j}: score {scores[j]!r} vs "
                    f"reference {ref[c]!r} (tolerance {tol[c]:.3g})")
            if abs(ref[c] - ref[order[j]]) > 2 * t:
                raise AssertionError(
                    f"{name} q{qi} rank {j}: pid {pid} (reference "
                    f"{ref[c]!r}) where the reference ranks "
                    f"{cand[order[j]]} ({ref[order[j]]!r})")
    log(f"check {name}: {len(answers)} queries match the float64 "
        f"reference; worst error {worst:.3f} of tolerance")


def same_answers(name, a, b, refs):
    """Two served answer sets for the same queries agree: scores within
    twice the tolerance (the reference's, or the float32 bound at the
    answers' own magnitude where there is no reference), and equal pids
    at every rank except inside a near-tie."""
    n_bitwise = 0
    for qi, ((pa, sa), (pb, sb)) in enumerate(zip(a, b)):
        fin = np.isfinite(sb)
        if refs is not None:
            tol = float(refs[qi][2].max())
        else:
            tol = 2 * (DIM + Q_LEN) * U32 * np.abs(sb[fin]).max(initial=1.0)
        if pa.shape != pb.shape or not np.array_equal(fin, np.isfinite(sa)) \
                or np.abs(sa[fin] - sb[fin]).max(initial=0.0) > 2 * tol:
            raise AssertionError(f"{name} q{qi}: scores differ beyond "
                                 f"{2 * tol:.3g}")
        near = np.zeros(len(sb), bool)
        close = np.abs(np.diff(np.where(fin, sb, -1e300))) <= 2 * tol
        near[1:] |= close
        near[:-1] |= close
        if np.any((pa != pb) & ~near):
            raise AssertionError(f"{name} q{qi}: pids differ outside "
                                 f"near-ties")
        n_bitwise += np.array_equal(pa, pb) and np.array_equal(sa, sb)
    log(f"check {name}: {len(a)} queries agree ({n_bitwise} bitwise)")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def tail_programs_have_kernel(retr):
    """The compiled stage-4 tails the served path dispatches (the fused
    rerank tail and the hybrid tail, at served shapes and static
    arguments) and the Pallas stage 1 must contain the Mosaic kernel."""
    from repro.core.plaid import fused_hybrid_tail
    from repro.index.splade_device import _score_topk
    from repro.kernels.fused_rerank.ops import fused_rerank_topk_batch

    sr = retr.searcher
    idx = sr.index
    C, Ld = retr.params.first_k, idx.doc_maxlen
    pd = idx.dim * idx.nbits // 8

    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)
    q, qv = s((MAX_BATCH, Q_LEN, idx.dim), jnp.float32), s((MAX_BATCH, Q_LEN),
                                                           jnp.bool_)
    packed = s((MAX_BATCH, C, Ld, pd), jnp.uint8)
    codes = s((MAX_BATCH, C, Ld), jnp.int32)
    valid, cmask = s((MAX_BATCH, C, Ld), jnp.bool_), s((MAX_BATCH, C),
                                                        jnp.bool_)
    texts = {
        "fused_rerank": fused_rerank_topk_batch.lower(
            q, packed, codes, valid, cmask, sr.centroids, sr.bucket_weights,
            nbits=idx.nbits, k=K, q_valid=qv).compile().as_text(),
        "hybrid_tail": fused_hybrid_tail.lower(
            q, packed, codes, valid, cmask, sr.centroids, sr.bucket_weights,
            qv, s((MAX_BATCH, C), jnp.float32), s((MAX_BATCH,), jnp.float32),
            nbits=idx.nbits, k=K, b=MAX_BATCH,
            normalizer=retr.params.normalizer).compile().as_text()}
    cache = retr.splade_device_cache()
    texts["splade_pallas"] = _score_topk.lower(
        cache.pids, cache.imps, s((MAX_BATCH, 16), jnp.int32),
        s((MAX_BATCH, 16), jnp.float32), s((), jnp.float32),
        n_docs=cache.n_docs, k=retr.params.first_k, impl="pallas",
        block_d=cache.block_d, chunk=cache.chunk).compile().as_text()
    for name, text in texts.items():
        if "tpu_custom_call" not in text:
            raise AssertionError(f"{name}: no tpu_custom_call in the "
                                 f"compiled program")
        log(f"kernel {name}: tpu_custom_call present")


def fused_vs_split(retr, queries):
    """Informational: do the fused and split tails agree bitwise here?"""
    args = dict(q_embs=list(queries["q_embs"][:MAX_BATCH]),
                term_ids=list(queries["term_ids"][:MAX_BATCH]),
                term_weights=list(queries["term_weights"][:MAX_BATCH]), k=K)
    for method in ("rerank", "hybrid"):
        outs = {}
        for tail in ("fused", "split"):
            retr.set_rerank_backend(tail)
            outs[tail] = retr.search_batch(method, **args)
        retr.set_rerank_backend("fused")
        same = all(np.array_equal(a, b) for a, b in zip(outs["fused"],
                                                        outs["split"]))
        log(f"fused vs split {method}: bitwise equal = {same}")


def one_chip(base, queries):
    from repro.launch.serve import build_or_load

    _, index, retr = build_or_load(str(base), "mmap")
    t0 = time.perf_counter()
    warm(retr, queries)
    log(f"warm-up compiles: {time.perf_counter() - t0:.1f} s")
    answers, health = serve(retr, queries, METHODS, depth=1)
    log_stages(health)
    for method in ("rerank", "hybrid"):
        check(f"{method} depth=1", answers[method],
              reference(retr, index, queries, method, "host"))
    deep, _ = serve(retr, queries, ("hybrid",), depth=2)
    check("hybrid depth=2", deep["hybrid"],
          reference(retr, index, queries, "hybrid", "host"))
    retr.set_splade_backend("pallas")
    warm(retr, queries)
    pal, health = serve(retr, queries, ("hybrid",), depth=1)
    log_stages(health)
    check("hybrid stage1=pallas", pal["hybrid"],
          reference(retr, index, queries, "hybrid", "pallas"))
    fused_vs_split(retr, queries)
    tail_programs_have_kernel(retr)


def direct_answers(retr, queries, method):
    """Answers straight from ``search_batch`` in full micro-batches."""
    out = []
    for lo in range(0, N_REQUESTS, MAX_BATCH):
        sl = slice(lo, lo + MAX_BATCH)
        pids, scores = retr.search_batch(
            method, q_embs=list(queries["q_embs"][sl]),
            term_ids=list(queries["term_ids"][sl]),
            term_weights=list(queries["term_weights"][sl]), k=K)
        out += [(np.asarray(p, np.int64), np.asarray(s, np.float64))
                for p, s in zip(pids, scores)]
    return out


def four_chips(base, queries):
    from repro.launch.serve import build_or_load

    devs = jax.devices()[:4]
    _, index, one = build_or_load(str(base), "mmap", splade_backend="pallas")
    _, _, group = build_or_load(str(base), "mmap", splade_backend="pallas",
                                n_shards=4, shard_workers="thread")
    for i, sh in enumerate(group.shards):
        cache = sh.splade_device_cache()
        arrays = {"centroids": sh.searcher.centroids,
                  "ivf": sh.searcher.ivf_padded,
                  "bucket_weights": sh.searcher.bucket_weights,
                  "splade_pids": cache.pids, "splade_imps": cache.imps}
        for name, arr in arrays.items():
            if arr.devices() != {devs[i]}:
                raise AssertionError(f"shard {i} {name} on "
                                     f"{arr.devices()}, not {devs[i]}")
        log(f"shard {i}: device arrays on {devs[i]}")
    t0 = time.perf_counter()
    warm(group, queries)
    log(f"warm-up compiles: {time.perf_counter() - t0:.1f} s")
    served, health = serve(group, queries, METHODS, depth=1)
    log_stages(health)
    for method in METHODS:
        refs = None
        if method in ("rerank", "hybrid"):
            refs = reference(one, index, queries, method, "pallas")
            check(f"{method} 4 shards", served[method], refs)
        same_answers(f"{method} 4 shards vs 1 shard", served[method],
                     direct_answers(one, queries, method), refs)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--n-docs", type=int, default=65_536,
                    help=f"passages (published-width corpus; at least "
                         f"{MIN_DOCS})")
    args = ap.parse_args()
    if args.n_docs < MIN_DOCS:
        ap.error(f"--n-docs below {MIN_DOCS}")
    from repro.launch.compile_cache import enable_compile_cache

    d0 = check_device(args.chips)
    log(f"compile cache: {enable_compile_cache()}")
    if args.n_docs != 65_536:
        log(f"corpus cut: {args.n_docs} passages instead of 65536")
    dot_precision()
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        base = pathlib.Path(tmp)
        queries = build_indexes(base, args.n_docs, args.seed)
        (four_chips if args.chips == 4 else one_chip)(base, queries)
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
