"""Serving launcher: bring up the concurrent ColBERT-serve stack.

    PYTHONPATH=src python -m repro.launch.serve \
        [--method hybrid] [--threads 1] [--port 8080] [--qps 2.0]

Builds (or loads with --index-dir) a ColBERT + SPLADE index, starts the
worker pool and the TCP front, and either serves forever (--port) or
runs a bounded Poisson load and prints the latency report.
"""

from __future__ import annotations

import argparse
import pathlib
import tempfile
import threading

import numpy as np

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.core.sharded import build_shard_group
from repro.core.store import PAGE_BYTES
from repro.data.synth import SynthCfg, make_corpus
from repro.index.builder import ColBERTIndex, build_colbert_index
from repro.index.sharding import split_index_tree
from repro.index.splade_index import SpladeIndex, build_splade_index
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import shard_device_map
from repro.serving.admission import AdmissionController
from repro.serving.context import CacheHierarchy
from repro.serving.engine import Request, ServeEngine
from repro.serving.loadgen import (
    load_trace,
    run_open_loop,
    run_poisson_load,
    zipf_trace,
)
from repro.serving.server import RetrievalServer


def build_or_load(index_dir: str | None, mode: str,
                  splade_backend: str = "host",
                  splade_max_df: int | None = None,
                  rerank_backend: str = "fused",
                  n_shards: int = 1, shard_workers: str = "thread",
                  shard_transport: str | None = None,
                  arena_bytes: int | None = None,
                  replicas: int = 1,
                  replica_endpoints: str | None = None,
                  allow_degraded: bool = False,
                  op_deadline_ms: float | None = None,
                  hedge_factor: float = 0.0,
                  hedge_floor_ms: float = 50.0):
    """Build (or load) the serving index and retriever. ``n_shards >= 2``
    splits the single index into a contiguous-range shard group on disk
    (``<dir>/shards/``, reused if already split at this count) and
    returns a scatter-gather retriever over it: ``shard_workers=
    "thread"`` keeps the group in this process (stage-1 device caches
    mapped round-robin onto the local devices); ``"process"`` spawns
    one shared-nothing worker process per shard (own mmap segment, own
    page cache, own GIL) behind an RPC coordinator — results are
    bitwise-identical across both backends. ``shard_transport`` picks
    the process-worker tensor path (``shm`` zero-copy ring arenas /
    ``socket`` stream; None = platform default) and ``arena_bytes``
    sizes each worker's per-direction ring.

    The replica knobs (process workers only) configure the fleet
    fabric: ``replicas`` local workers per shard plus any
    ``replica_endpoints`` (``"h:p,h:p;h:p"`` — ``;`` between shards,
    ``,`` between that shard's remote workers), health-aware failover
    between them, ``op_deadline_ms`` per-op deadlines, hedged requests
    past ``hedge_factor``× the replica's EWMA latency, and
    ``allow_degraded`` partial answers when every replica of a shard
    is down."""
    if index_dir and (pathlib.Path(index_dir) / "colbert").exists():
        base = pathlib.Path(index_dir)
        corpus = None
    else:
        cfg = SynthCfg(n_docs=3000, n_queries=300, seed=0)
        corpus = make_corpus(cfg)
        base = pathlib.Path(index_dir or tempfile.mkdtemp(prefix="serve_"))
        build_colbert_index(base / "colbert", corpus["doc_embs"],
                            corpus["doc_lens"], nbits=4,
                            n_centroids=256, kmeans_iters=4)
        build_splade_index(corpus["doc_term_ids"],
                           corpus["doc_term_weights"], cfg.vocab,
                           cfg.n_docs).save(base / "splade")
    plaid_params = PlaidParams(nprobe=4, candidate_cap=1024, ndocs=256)
    ms_params = MultiStageParams(first_k=200, alpha=0.3,
                                 splade_backend=splade_backend,
                                 splade_max_df=splade_max_df,
                                 rerank_backend=rerank_backend)
    if n_shards > 1 or shard_workers == "process":
        from repro.index.sharding import load_group
        group = split_index_tree(base, n_shards)
        shard_dirs, boundaries = load_group(group)
        fleet_kw = {}
        if shard_workers == "process":
            fleet_kw = dict(replicas=replicas,
                            replica_endpoints=replica_endpoints,
                            allow_degraded=allow_degraded,
                            op_deadline_ms=op_deadline_ms,
                            hedge_factor=hedge_factor,
                            hedge_floor_ms=hedge_floor_ms)
        retr = build_shard_group(
            shard_dirs, boundaries, workers=shard_workers, mode=mode,
            plaid_params=plaid_params, multistage_params=ms_params,
            transport=shard_transport, arena_bytes=arena_bytes,
            devices=(None if shard_workers == "process"
                     else shard_device_map(n_shards)), **fleet_kw)
        # the unsharded index handle is informational only (pool-size
        # print) — serving reads the per-shard segments, so always open
        # it mmap: a second full-RAM copy of the pool would double
        # resident memory under --mode ram
        return corpus, ColBERTIndex(base / "colbert", mode="mmap"), retr
    index = ColBERTIndex(base / "colbert", mode=mode)
    sidx = SpladeIndex.load(base / "splade", mmap=(mode == "mmap"))
    retr = MultiStageRetriever(sidx, PLAIDSearcher(index, plaid_params),
                               ms_params)
    return corpus, index, retr


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--index-dir", default=None)
    ap.add_argument("--mode", default="mmap", choices=["mmap", "ram"])
    ap.add_argument("--method", default="hybrid")
    ap.add_argument("--threads", type=int, default=1)
    ap.add_argument("--splade-backend", default="host",
                    choices=["host", "jax", "pallas"],
                    help="stage-1 scorer: host CSR pass, device "
                         "segment-sum, or the Pallas block kernel")
    ap.add_argument("--splade-max-df", type=int, default=None,
                    help="padded-postings df cap for jax/pallas "
                         "(memory vs exactness; default: exact)")
    ap.add_argument("--rerank-backend", default="fused",
                    choices=["fused", "split"],
                    help="stage-4 tail: fused = decompress + MaxSim + "
                         "top-k in ONE device dispatch (the tiled "
                         "fused_rerank kernel on TPU, a fused XLA tail "
                         "elsewhere), split = the legacy multi-dispatch "
                         "tail")
    ap.add_argument("--shards", type=int, default=1,
                    help=">=2: partition the index into this many "
                         "contiguous doc-range shards (scatter-gather "
                         "serving with a global top-k merge; per-shard "
                         "mmap segments fault pages in parallel)")
    ap.add_argument("--shard-workers", default="thread",
                    choices=["thread", "process"],
                    help="shard group backend: in-process thread "
                         "fanouts, or one shared-nothing worker "
                         "process per shard (own mmap page cache + "
                         "GIL) behind the scatter-gather RPC — "
                         "bitwise-identical results")
    ap.add_argument("--shard-transport", default=None,
                    choices=["shm", "socket"],
                    help="process-worker tensor transport: shm = "
                         "zero-copy shared-memory ring arenas (one per "
                         "worker, /dev/shm), socket = in-frame sendmsg "
                         "segments over the socketpair; default picks "
                         "shm when /dev/shm is writable")
    ap.add_argument("--arena-bytes", type=int, default=None,
                    help="per-direction ring capacity of each worker's "
                         "shm arena (bounds in-flight tensor bytes; "
                         "default auto-sizes, see launch.mesh."
                         "shard_arena_bytes)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="local worker processes per shard (process "
                         "workers only; >=2 enables health-aware "
                         "failover between interchangeable replicas)")
    ap.add_argument("--replica-endpoints", default=None,
                    help="remote standalone workers per shard, "
                         "'host:port,host:port;host:port' — ';' "
                         "separates shards, ',' that shard's remote "
                         "replicas (each runs `python -m repro.serving"
                         ".worker --shard-dir … --port …`)")
    ap.add_argument("--allow-degraded", action="store_true",
                    help="when every replica of a shard is down, "
                         "serve partial results merged over the "
                         "surviving shards (responses carry degraded="
                         "true + the missing shard ids) instead of "
                         "failing the request")
    ap.add_argument("--op-deadline-ms", type=float, default=None,
                    help="per-op RPC deadline; an expired op fails "
                         "over to a sibling replica (or raises "
                         "DeadlineExceeded with one replica)")
    ap.add_argument("--hedge-factor", type=float, default=0.0,
                    help=">0 hedges stragglers: an op still pending "
                         "past factor×EWMA of its replica's latency "
                         "is re-sent on a sibling (shard ops are "
                         "pure, so duplicates are safe)")
    ap.add_argument("--hedge-floor-ms", type=float, default=50.0,
                    help="minimum hedge budget, so cold EWMAs don't "
                         "hedge every op")
    ap.add_argument("--max-batch", type=int, default=1)
    ap.add_argument("--batch-timeout-ms", type=float, default=2.0,
                    help="how long a micro-batch stays open for arrivals "
                         "while the engine is full; a batch the engine "
                         "can start goes out at once")
    ap.add_argument("--latency-slo-ms", type=float, default=None,
                    help="enable adaptive micro-batch sizing against "
                         "this service-time SLO")
    ap.add_argument("--pipeline", action="store_true",
                    help="stage-graph pipelining at the default depth "
                         "(2, double-buffered)")
    ap.add_argument("--pipeline-depth", type=int, default=None,
                    help="batches in flight: 1 = synchronous, "
                         ">=2 overlaps micro-batch N+1's mmap gather "
                         "with batch N's device dispatch")
    ap.add_argument("--pipeline-workers", default="single",
                    choices=["single", "kind"],
                    help="executor scheduling: single-worker software "
                         "pipelining (async dispatch; best under the "
                         "GIL) or per-kind host/device worker threads "
                         "(multi-core hosts / TPU)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="strictly open-loop Poisson arrivals at this "
                         "QPS (instead of the default generator)")
    ap.add_argument("--cache-exact", type=int, default=0,
                    help="exact result cache entries (0 = off): a hit "
                         "returns the bitwise cold answer straight "
                         "from the front door")
    ap.add_argument("--cache-stage1", type=int, default=0,
                    help="stage-1/candidate cache entries (0 = off): "
                         "cached SPLADE unions / PLAID candidate sets "
                         "skip the stage-1 dispatch on repeat queries")
    ap.add_argument("--admission-slo-ms", type=float, default=None,
                    help="SLO-aware admission: when per-stage EWMAs "
                         "predict a request blows this budget, degrade "
                         "it to the splade-only plan or shed it")
    ap.add_argument("--shed-factor", type=float, default=3.0,
                    help="shed when even the degraded plan is "
                         "predicted past factor×SLO")
    ap.add_argument("--skew", type=float, default=0.0,
                    help="Zipf skew of the bounded load's query "
                         "sampling (0 = round-robin; >0 draws queries "
                         "with popularity ∝ 1/rank^skew — the repeat-"
                         "heavy traffic caches are for)")
    ap.add_argument("--replay", default=None,
                    help="replay a query-index trace file (one index "
                         "per line) instead of sampling")
    ap.add_argument("--live", action="store_true",
                    help="enable the mutable index: upsert/delete/"
                         "compact ops on the TCP front (new docs land "
                         "in an in-RAM delta segment, deletes are "
                         "tombstones filtered at the merges; needs "
                         "--mode mmap)")
    ap.add_argument("--live-compact-every", type=int, default=None,
                    help="background compaction threshold: merge the "
                         "delta segment into a new index generation "
                         "whenever it reaches this many docs (implies "
                         "--live)")
    ap.add_argument("--port", type=int, default=None,
                    help="serve forever on this TCP port (0 binds an "
                         "ephemeral port and prints the real one); "
                         "omit to run the bounded load test instead")
    ap.add_argument("--qps", type=float, default=2.0)
    ap.add_argument("--n", type=int, default=60)
    args = ap.parse_args()
    enable_compile_cache()

    depth = (args.pipeline_depth if args.pipeline_depth is not None
             else (2 if args.pipeline else 1))
    corpus, index, retr = build_or_load(
        args.index_dir, args.mode, args.splade_backend,
        args.splade_max_df, rerank_backend=args.rerank_backend,
        n_shards=args.shards,
        shard_workers=args.shard_workers,
        shard_transport=args.shard_transport,
        arena_bytes=args.arena_bytes,
        replicas=args.replicas,
        replica_endpoints=args.replica_endpoints,
        allow_degraded=args.allow_degraded,
        op_deadline_ms=args.op_deadline_ms,
        hedge_factor=args.hedge_factor,
        hedge_floor_ms=args.hedge_floor_ms)
    # backend already configured (and device cache pre-materialised) via
    # MultiStageParams in build_or_load; the engine owns the retriever so
    # a process shard group's workers are reaped on every exit path
    compactor = None
    if args.live or args.live_compact_every is not None:
        retr.enable_live()
        if args.live_compact_every is not None:
            from repro.index.live import AutoCompactor
            compactor = AutoCompactor(retr, args.live_compact_every)
            compactor.start()
    caches = None
    if args.cache_exact > 0 or args.cache_stage1 > 0:
        caches = CacheHierarchy(exact_entries=args.cache_exact,
                                stage1_entries=args.cache_stage1)
    admission = None
    if args.admission_slo_ms is not None:
        admission = AdmissionController(args.admission_slo_ms,
                                        shed_factor=args.shed_factor)
    engine = ServeEngine(retr, pipeline_depth=depth,
                         pipeline_workers=args.pipeline_workers,
                         own_retriever=True, caches=caches)
    server = RetrievalServer(
        engine, n_threads=args.threads, max_batch=args.max_batch,
        batch_timeout_ms=args.batch_timeout_ms,
        latency_slo_ms=args.latency_slo_ms, admission=admission)
    server.start()
    print(f"serving ({args.mode} index, {args.threads} thread(s), "
          f"stage1={args.splade_backend}, rerank={args.rerank_backend}, "
          f"pipeline_depth={depth}, "
          f"shards={args.shards} [{args.shard_workers} workers]); "
          f"pool={index.store.total_bytes() / 1e6:.1f} MB")

    try:
        if args.port is not None:
            tcp = server.serve_tcp("0.0.0.0", args.port)
            server.install_sigterm_handler()   # graceful drain on TERM
            print(f"TCP front on :{server.tcp_port} (newline-delimited "
                  f"JSON; SIGTERM or Ctrl-C to stop)")
            try:
                tcp.serve_forever()
            except KeyboardInterrupt:
                pass
            finally:
                server.shutdown_gracefully()
            return

        assert corpus is not None, \
            "the bounded load test needs a built-in corpus"
        n_unique = len(corpus["q_embs"])
        if args.replay is not None:
            trace = load_trace(args.replay) % n_unique
            trace = trace[:args.n] if len(trace) >= args.n else \
                np.resize(trace, args.n)
        elif args.skew > 0:
            trace = zipf_trace(args.n, n_unique, skew=args.skew, seed=0)
        else:
            trace = np.arange(args.n) % n_unique
        reqs = [Request(qid=i, method=args.method,
                        q_emb=corpus["q_embs"][q],
                        term_ids=corpus["q_term_ids"][q],
                        term_weights=corpus["q_term_weights"][q],
                        k=20, trace_id=int(q))
                for i, q in enumerate(trace)]
        if args.arrival_rate is not None:
            res = run_open_loop(server, reqs,
                                arrival_rate=args.arrival_rate, seed=0)
        else:
            res = run_poisson_load(server, reqs, qps=args.qps, seed=0,
                                   burst=args.max_batch)
        s = res.summary()
        print(f"offered {s['offered_qps']:.2f} QPS → achieved "
              f"{s['achieved_qps']:.2f}; p50 {s['p50'] * 1e3:.1f} ms, "
              f"p95 {s['p95'] * 1e3:.1f} ms, p99 {s['p99'] * 1e3:.1f} ms")
        print(f"trace: {s['unique_queries']} unique / "
              f"{s['repeat_queries']} repeats; outcomes: "
              f"{s['cache_hits']} cache hits, {s['degraded']} degraded, "
              f"{s['shed']} shed, {s['failed']} failed")
        if caches is not None:
            cs = caches.stats()
            print(f"caches: exact {cs['exact']['hits']}h/"
                  f"{cs['exact']['misses']}m "
                  f"(size {cs['exact']['size']}/"
                  f"{cs['exact']['capacity']}), stage1 "
                  f"{cs['stage1']['hits']}h/{cs['stage1']['misses']}m "
                  f"(size {cs['stage1']['size']}/"
                  f"{cs['stage1']['capacity']})")
        if admission is not None:
            ast = admission.stats()
            print(f"admission: {ast['full_admits']} full, "
                  f"{ast['degraded_admits']} degraded, "
                  f"{ast['sheds']} shed "
                  f"(SLO {ast['latency_slo_ms']:.0f} ms)")
        if depth > 1:
            h = server.health()
            print(f"pipeline overlap: "
                  f"{100 * h.get('overlap_fraction', 0.0):.1f}% "
                  f"(stage queues: {h['pipeline']['queues']})")
        if hasattr(retr, "worker_health"):
            # process group: the aggregate pool is split across worker
            # working sets, not replicated into the coordinator
            for w in retr.worker_health():
                print(f"shard worker {w['shard']}: pid={w['pid']} "
                      f"rss={w.get('rss_bytes', 0) / 1e6:.1f} MB "
                      f"segment={w.get('pool_bytes', 0) / 1e6:.1f} MB "
                      f"served={w.get('served', 0)} "
                      f"transport={w.get('transport', '?')} "
                      f"copied={w.get('rpc_bytes_copied', 0) / 1e6:.2f}"
                      f" MB zero_copy="
                      f"{w.get('rpc_bytes_zero_copy', 0) / 1e6:.2f} MB")
        else:
            # in-process serving: the gathers hit this process's stores
            # (per-shard segments under thread sharding)
            stores = ([sh.searcher.index.store for sh in retr.shards]
                      if hasattr(retr, "shards") else [index.store])
            touched = sum(len(s.stats.unique_pages or ())
                          for s in stores)
            total = sum(max(1, s.total_bytes() // PAGE_BYTES)
                        for s in stores)
            print(f"mmap working set: {100 * touched / total:.1f}% of "
                  f"pool" + (f" ({len(stores)} segments)"
                             if len(stores) > 1 else ""))
        server.drain()
        server.stop()
    finally:
        if compactor is not None:
            compactor.stop()
        engine.close()     # stops pipelines + reaps shard workers


if __name__ == "__main__":
    main()
