"""Set-up child: draw a run's corpus from its seed and build the index.

    python bench/build.py --config <file> --seed <n> --out <dir>

Runs in its own process, before the serving process touches JAX: the
index builders compute k-means and the residual codes on the chip, and a
chip belongs to one process at a time. The corpus is handed to the
program's own builders (``build_colbert_index``, ``build_splade_index``)
as host arrays, and the index is written under ``<out>/colbert`` and
``<out>/splade``. With ``--warm-k`` it then opens the index as the
serving process will and compiles the cell's programs into the
persistent cache, so that the serving process loads every program from
the cache and holds no compiler memory: its anonymous memory then reads
the same in a checkout's first run as in later ones. The last line of
standard output is a JSON object with the device JAX found and the
seconds each step took; with
``--require-tpu`` it exits with code 3, before drawing anything, where
JAX finds no TPU or fewer chips than ``--chips``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import gen  # noqa: E402
import harness  # noqa: E402


def preload(retr, corpus: dict, docs: dict, writes: dict, seed: int):
    """Apply the traffic's ``preload`` updates to a live retriever
    directly, as the load generator sends them over TCP in a run, so
    that the warm-up compiles the upsert encoding at their lengths and
    the overlay path → the new versions' terms."""
    keys = gen.write_keys(corpus, writes, writes["preload"], seed)
    v = gen.make_versions(corpus, docs, keys, seed)
    current = {}
    for j, key in enumerate(keys.tolist()):
        pid = retr.live_upsert(v["embs"][j, :v["lens"][j]],
                               v["term_ids"][j], v["term_weights"][j])
        retr.live_delete(current.get(key, key))
        current[key] = pid
    return {"term_ids": v["term_ids"], "term_weights": v["term_weights"]}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--chips", type=int, default=1,
                    help="exit with code 3 before any work when JAX sees "
                         "fewer devices, or no TPU with --require-tpu")
    ap.add_argument("--require-tpu", action="store_true")
    ap.add_argument("--warm-k", type=int, default=0,
                    help="compile the serving programs for answers of "
                         "this depth once the index is built")
    ap.add_argument("--traffic", default=None,
                    help="with --warm-k and a live configuration, the "
                         "traffic whose preload the warm-up applies")
    args = ap.parse_args(argv)
    cfg = json.loads(pathlib.Path(args.config).read_text())
    harness.use_compile_cache()
    import jax

    from repro.index.builder import build_colbert_index
    from repro.index.splade_index import build_splade_index

    dev = harness.device_info(jax.devices())
    if ((args.require_tpu and dev["platform"] != "tpu")
            or dev["count"] < args.chips):
        print(json.dumps({"device": dev}), flush=True)
        sys.exit(3)
    out = pathlib.Path(args.out)
    corpus = harness.corpus(cfg)
    times = {}
    t0 = time.perf_counter()
    docs = gen.make_corpus(corpus, args.seed)
    embs = gen.make_doc_embs(corpus, docs, args.seed)
    times["corpus_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    build_colbert_index(out / "colbert", embs, docs["doc_lens"],
                        nbits=cfg["index"]["nbits"],
                        n_centroids=cfg["n_centroids"],
                        seed=gen.seed_word(args.seed) % (1 << 31))
    del embs
    times["colbert_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    build_splade_index(docs["doc_term_ids"], docs["doc_term_weights"],
                       corpus["vocab"], corpus["n_docs"]).save(out / "splade")
    times["splade_s"] = time.perf_counter() - t0
    if args.warm_k:
        import run
        t0 = time.perf_counter()
        retr, _ = run.open_retriever(cfg, out)
        terms = None
        if run.go_live(retr, cfg) is not None:
            terms = preload(retr, corpus, docs, harness.load_json(
                args.traffic)["writes"], args.seed)
        run.warm(retr, cfg, args.warm_k, terms)
        times["compile_s"] = time.perf_counter() - t0
    print(json.dumps({"device": dev, "times": times}), flush=True)


if __name__ == "__main__":
    main()
