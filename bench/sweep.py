"""Find a configuration's knee: the highest offered rate the served path
keeps up with, by a sweep of open-loop windows at fixed rates.

    python bench/sweep.py --workload <cell> --seed <n>
        --rates <r> [<r> ...] [--seconds <s>]

One process builds the cell's index (child), opens and warms it as a run
does, and serves one window per rate, in the order given, each from a
fresh load generator with that rate in place of the cell's and the same
seed. A window lasts ``run_seconds`` unless ``--seconds`` says longer.
Per rate it prints one JSON line: offered and completed requests per
second, p50/p95 latency, the median latency of the window's first and
last fifth, and whether the rate passes. The first rate is the low
load: its p50 is the yardstick. A rate passes when every request was
answered, the completed rate keeps up with the offered one (at least
``KEEP_UP`` of it), the last fifth's p50 is at most ``GROWTH`` times the
first fifth's (no growing backlog), and the p50 is at most ``SLOWDOWN``
times the low load's. The sweep stops at the first rate that fails; the
knee is the rate before it, and the last line names it. The benchmark's
runs never run this; its rates go into the traffic files as numbers.

A live configuration (``serving.live``) is served as a run serves it:
the first window's load generator sends the traffic's ``preload``
updates before the overlay path is warmed and the compactor starts, and
each later window continues from the writes of the one before (each
key's current pid is handed on in ``keys.npy``). Failed writes fail a
rate.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
import threading
import time

import numpy as np

import harness
import run
import stats


KEEP_UP = 0.97
GROWTH = 1.5
SLOWDOWN = 2.0


def passes(row: dict, low_p50_ms: float) -> bool:
    return (row["failed"] == 0
            and row["completed_qps"] >= KEEP_UP * row["offered"]
            and row["p50_last_fifth_ms"] <= GROWTH * row["p50_first_fifth_ms"]
            and row["p50_ms"] <= SLOWDOWN * low_p50_ms)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=0.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    args.seconds = max(args.seconds, harness.benchmark()["run_seconds"])
    c = harness.cell(args.workload)
    harness.use_compile_cache()
    work = harness.WORK / f"sweep-{args.workload}"
    index_dir = work / "index"
    run.build_index(c, args.seed, index_dir, True)
    import jax  # noqa: F401

    from repro.serving.engine import ServeEngine
    from repro.serving.server import RetrievalServer

    cfg, s = c["config"], c["config"]["serving"]
    retr, _ = run.open_retriever(cfg, index_dir)
    compactor = run.go_live(retr, cfg)
    if compactor is None:
        run.warm(retr, cfg, c["traffic"]["k"])
    keys = work / "keys.npy"
    keys.unlink(missing_ok=True)
    engine = ServeEngine(retr, pipeline_depth=s["pipeline_depth"])
    server = RetrievalServer(engine, max_batch=s["max_batch"],
                             batch_timeout_ms=s["batch_timeout_ms"])
    server.start()
    tcp = server.serve_tcp("127.0.0.1", 0)
    threading.Thread(target=tcp.serve_forever, daemon=True).start()
    low_p50 = knee = None
    try:
        for i, rate in enumerate(args.rates):
            tr = work / f"traffic-{i}.json"
            tr.write_text(json.dumps(dict(c["traffic"], rate_per_s=rate)))
            out = work / f"client-{i}.npz"
            client = subprocess.Popen(
                [sys.executable, str(harness.HERE / "client.py"),
                 "--config", str(c["config_file"]), "--traffic", str(tr),
                 "--seed", str(args.seed), "--seconds",
                 repr(args.seconds), "--out", str(out), "--drain", "20",
                 "--keys", str(keys)],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True)
            client.stdout.readline()
            if compactor is not None and i == 0:
                run.warm(retr, cfg, c["traffic"]["k"],
                         run.preload(client, server.tcp_port))
                compactor.start()
            live0 = retr.live_stats()
            retr.reset_stage_stats()
            t0 = time.monotonic() + 0.5
            client.stdin.write(f"GO {server.tcp_port} {t0!r}\n")
            client.stdin.flush()
            client.wait()
            with np.load(out) as z:
                rec = {k: z[k] for k in z.files}
            lat = stats.latencies_ms(rec)
            fifth = len(lat) // 5
            full = retr.pipeline_stats.snapshot()
            snap = full["stages"]
            first = snap.get(retr.compile_plan(s["method"]).stages[0].name,
                             {})
            row = {
                "rate": rate, "offered": len(lat) / args.seconds,
                "completed_qps": stats.qps(rec, args.seconds),
                "failed": stats.failed(rec),
                "writes_acked": stats.writes_acked(rec),
                "jax_compiles": full["counters"].get("jax_compiles", 0),
                "compactions": (retr.live_stats().get("compactions", 0)
                                - live0.get("compactions", 0)),
                "p50_ms": stats.percentile_ms(rec, 50),
                "p95_ms": stats.percentile_ms(rec, 95),
                "p50_first_fifth_ms": float(np.median(lat[:fifth])),
                "p50_last_fifth_ms": float(np.median(lat[-fifth:])),
                "batch_fill": (first.get("queries", 0)
                               / max(first.get("dispatches", 0), 1)),
                "stage_ms_per_batch": {
                    k: v["wall_s"] / max(v["dispatches"], 1) * 1e3
                    for k, v in snap.items()}}
            if low_p50 is None:
                low_p50 = row["p50_ms"]
            row["passes"] = passes(row, low_p50)
            print(json.dumps(row), flush=True)
            if not row["passes"]:
                break
            knee = rate
            # the next rate starts on an empty queue
            server.drain()
    finally:
        if compactor is not None and compactor.ident is not None:
            run.stop_compactor(compactor)
        server.shutdown_gracefully()
        tcp.server_close()
        engine.close()
    print(json.dumps({"workload": args.workload, "knee": knee,
                      "low_load_p50_ms": low_p50}), flush=True)


if __name__ == "__main__":
    main()
