"""The control of the ``correct`` comparison: the reference, put in the
program's place and computed one precision step below what the
configuration states, must come out as not correct.

    python bench/control.py --workload <cell> --seeds <n> [<n> ...]
        [--precision high|highest | --forget-writes]

The configuration states float32 at ``Precision.HIGHEST`` for every dot
of the served path; the step below is ``high`` (three bf16 passes). For
each seed this builds the cell's index in this process, draws the
requests a run of ``run_seconds`` would send, and answers the sample a
run would compare with the reference's own pipeline in float32 on the
chip — SPLADE stage 1, the PLAID probe and approximate score, residual
decoding, MaxSim and the z-normalised fusion — its dots at
``--precision``. Those answers then go through the same comparison as
a run's (``reference.check``), and each number is printed beside the
cell's limit, one JSON line per seed. The benchmark's runs never run
this.

``--forget-writes`` is the control of the check under writes, for a
cell whose traffic has ``writes``: each seed is served as a run serves
it (``run.run``), and the answers are judged against the corpus as
built, as though no write had been made. It must come out as not
correct through ``bad_pids`` or ``rank_gap``, with nothing ``failed``.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import shutil
import subprocess
import sys

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import gen  # noqa: E402
import harness  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402

ROWS = 8192


def _dot(a, b, precision: str):
    """a @ b.T in float32. ``highest``: full float32. ``high``: the three
    bf16 passes ``hi·hi + hi·lo + lo·hi`` of each operand split into a
    bf16 head and a bf16 remainder — the same arithmetic on every
    backend, where the XLA flag means nothing off the TPU."""
    import jax
    import jax.numpy as jnp

    full = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST,
                             preferred_element_type=jnp.float32)
    if precision == "highest":
        return full(a, b.T)

    def split(x):
        hi = x.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    (ah, al), (bh, bl) = split(a), split(b)
    return full(ah, bh.T) + (full(ah, bl.T) + full(al, bh.T))


def _topk(x, k):
    """Indices of the k largest, ties by index."""
    return np.lexsort((np.arange(len(x)), -x))[:k]


def answers(cfg: dict, index_dir, seed: int, n: int, sample, k: int,
            precision: str, rel=None):
    """Answers of the sampled requests → (pids (n, k), scores (n, k));
    ``rel`` as ``gen.make_queries`` takes it."""
    import jax
    import jax.numpy as jnp

    dot = jax.jit(functools.partial(_dot, precision=precision))
    corpus, s = harness.corpus(cfg), cfg["serving"]
    docs = gen.make_corpus(corpus, seed)
    q = gen.make_queries(corpus, docs, n, seed, rel)
    index = reference.Index(index_dir, corpus["dim"], cfg["index"]["nbits"],
                            docs["doc_lens"])
    cents = jnp.asarray(index.centroids, jnp.float32)

    def maxsim(qe, pids):
        rows, first = index.token_rows(pids)
        emb, _ = index.decode(rows)
        pad = np.zeros((-(-len(emb) // ROWS) * ROWS, emb.shape[1]),
                       np.float32)       # few shapes, few compiles
        pad[:len(emb)] = emb
        sim = np.asarray(dot(jnp.asarray(pad), qe))[:len(emb)]
        return np.maximum.reduceat(sim, first, axis=0).sum(1,
                                                           dtype=np.float32)

    splade = reference.Splade(docs)
    pids = np.full((n, k), -1, np.int64)
    scores = np.full((n, k), np.nan)
    for i in sample.tolist():
        qe = jnp.asarray(q["q_embs"][i])
        if s["method"] == "hybrid":
            st = np.zeros(splade.n_docs, np.float32)
            for t, w in zip(q["q_term_ids"][i], q["q_term_weights"][i]):
                pos = splade.order[splade.start[t]:splade.start[t + 1]]
                np.add.at(st, pos // splade.nnz, np.float32(
                    w * np.float32(splade.quantum))
                    * splade.imp[pos].astype(np.float32))
            cand = _topk(st, s["first_k"])
            c = maxsim(qe, cand)
            x = np.zeros(len(cand), np.float32)
            for v, a in ((st[cand], s["alpha"]), (c, 1 - s["alpha"])):
                x += np.float32(a) * (v - v.mean()) / max(v.std(), 1e-9)
            top = _topk(x, k)
            pids[i], scores[i] = cand[top], x[top]
            continue
        sc = np.asarray(dot(cents, qe)).T                    # (Lq, K)
        probe = np.argsort(-sc, axis=1)[:, :s["nprobe"]]
        hit = np.zeros(len(cents), bool)
        hit[probe.ravel()] = True
        cand = np.unique(index.tok_pid[hit[index.codes]])
        rows, first = index.token_rows(cand)
        approx = np.maximum.reduceat(sc[:, index.codes[rows]], first,
                                     axis=1).sum(0, dtype=np.float32)
        surv = cand[_topk(approx, harness.survivors(s))]
        ex = maxsim(qe, surv)
        top = _topk(ex, k)
        pids[i], scores[i] = surv[top], ex[top]
    return pids, scores


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="high",
                    choices=("high", "highest"))
    ap.add_argument("--forget-writes", action="store_true")
    args = ap.parse_args(argv)
    harness.use_compile_cache()
    import build

    c = harness.cell(args.workload)
    cfg, traffic = c["config"], c["traffic"]
    seconds = harness.benchmark()["run_seconds"]
    limits = cfg["check"]["limits"]
    if args.forget_writes and len(args.seeds) > 1:
        # a run holds the chip until its process ends: one process a seed
        for seed in args.seeds:
            subprocess.run([sys.executable, __file__, "--workload",
                            args.workload, "--seeds", str(seed),
                            "--forget-writes"], check=True)
        return
    if args.forget_writes:
        import run
        for seed in args.seeds:
            r = run.run(args.workload, seed, seconds, False,
                        forget_writes=True)
            print(json.dumps({
                "workload": args.workload, "seed": seed,
                "control": "forget_writes", "correct": r["correct"],
                "numbers": {m: x["value"] for m, x in r["checks"].items()},
                "limits": limits, "writes": r["info"].get("writes")}),
                flush=True)
        return
    n = harness.n_requests(traffic, seconds)
    out = harness.WORK / f"control-{args.workload}"
    for seed in args.seeds:
        shutil.rmtree(out, ignore_errors=True)
        build.main(["--config", str(c["config_file"]), "--seed", str(seed),
                    "--out", str(out)])
        client = {"status": np.zeros(n, np.int8)}
        sample = stats.sample(client, cfg["check"]["sample"], seed)
        client["pids"], client["scores"] = answers(
            cfg, out, seed, n, sample, traffic["k"], args.precision,
            gen.query_rel(harness.corpus(cfg), traffic, n, seed))
        numbers = reference.check(cfg, traffic, out, seed, client, sample)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "precision": args.precision, "numbers": numbers,
            "correct": all(numbers[m] <= v for m, v in limits.items()),
            "limits": limits}), flush=True)
    shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    main()
