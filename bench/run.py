"""Serve one benchmark cell once, on the chip, and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s>
        --trace <0|1>

Set-up (``setup_s``, from process start to the first due request):

1. a child process (``build.py``) draws the corpus from the seed and
   builds the ColBERT and SPLADE indexes with the program's builders
   into ``bench/.work/<cell>/index``, and in a checkout's first run
   compiles the serving programs into the cache; this process does not
   touch JAX until the child has exited, since a chip belongs to one
   process;
2. a second child (``client.py``, NumPy and sockets only) draws the
   queries and arrival schedule from the seed and encodes the requests;
3. meanwhile this process opens the index as ``launch/serve.py`` does
   (mmap pool, mmap SPLADE postings, the configuration's PLAID and
   multi-stage parameters), serves it through ``ServeEngine``,
   ``RetrievalServer`` and the TCP front on an ephemeral port, and warms
   the cell's method at every micro-batch size it can form, from the
   persistent compilation cache in ``bench/.cache/jax``.

A configuration with ``serving.live`` is served as ``launch/serve.py
--live --live-compact-every N`` serves it: the live index is attached,
the load generator sends the traffic's ``preload`` updates over the
TCP front, the overlay path that then serves every query is warmed at
every micro-batch size, and the ``AutoCompactor`` starts; it is stopped
once the window's replies are in.

The window: the client sends open-loop arrivals for ``--seconds`` and
times each request from its due time to its parsed reply. With
``--trace 1`` the profiler records the middle of the window, the
harness's own wrappers around the program's stages, TCP decoding and
the stage-4 tail annotate it, and the per-layer metrics are printed
instead of the end-to-end ones.

After the window the server is shut down, the device's peak memory is
read, and a seeded sample of the answers served over TCP is compared
with the float64 reference (``reference.py``). Each number compared and
its limit end standard error, and the result's last key. The last line
of standard output is the result as one JSON object. The run fails,
and prints no result, when JAX finds no TPU or fewer chips than the
cell asks for.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

import numpy as np  # noqa: E402

import harness  # noqa: E402
import layers  # noqa: E402
import reference  # noqa: E402
import stats  # noqa: E402

CLIENT_GRACE_S = 90.0     # past the window: drain, write the record


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def _check_device(dev: dict, chips: int, require_tpu: bool):
    if require_tpu and dev["platform"] != "tpu":
        raise NoChip(f"needs a TPU; JAX found platform {dev['platform']!r}")
    if dev["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{dev['count']}")


def build_index(c: dict, seed: int, out, require_tpu: bool) -> dict:
    """Set-up step 1, in a child process → its JSON report. In the
    cell's first run in a checkout, the child also compiles the serving
    programs into the cache, and a marker under ``bench/.cache/warmed/``
    records that it did."""
    chips = c["workload"]["chips"]
    marker = harness.CACHE.parent / "warmed" / c["workload"]["name"]
    cold = not marker.exists()
    proc = subprocess.run(
        [sys.executable, str(harness.HERE / "build.py"), "--config",
         str(c["config_file"]), "--seed", str(seed), "--out", str(out),
         "--chips", str(chips)] + (["--require-tpu"] if require_tpu else [])
        + (["--warm-k", str(c["traffic"]["k"]), "--traffic",
            str(c["traffic_file"])] if cold else []),
        stdout=subprocess.PIPE, text=True, cwd=harness.ROOT)
    if proc.returncode == 3:
        _check_device(json.loads(proc.stdout.strip().splitlines()[-1])[
            "device"], chips, require_tpu)
    if proc.returncode != 0:
        raise RuntimeError(f"index build failed (exit {proc.returncode})")
    if cold:
        marker.parent.mkdir(parents=True, exist_ok=True)
        marker.touch()
    return json.loads(proc.stdout.strip().splitlines()[-1])


def open_retriever(cfg: dict, index_dir):
    """The objects ``launch/serve.py``'s ``build_or_load`` opens for one
    shard in mmap mode, with the configuration's parameters.

    The IVF is padded to the power of two at or above its longest list
    (256 for every seed measured at the configurations' size), so its
    compiled shapes are the same from seed to seed. PLAID's candidate
    cap is the most its union can hold, query tokens × ``nprobe`` × that
    width: PLAID has no cap, and the served union is never cut.
    → (retriever, longest IVF list)."""
    from repro.core.multistage import MultiStageParams, MultiStageRetriever
    from repro.core.plaid import PLAIDSearcher, PlaidParams
    from repro.index.builder import ColBERTIndex
    from repro.index.splade_index import SpladeIndex

    s = cfg["serving"]
    index = ColBERTIndex(index_dir / "colbert", mode="mmap")
    if index.n_centroids != cfg["n_centroids"]:
        raise RuntimeError(f"index has {index.n_centroids} centroids, the "
                           f"configuration {cfg['n_centroids']}")
    longest = index.ivf.max_list_len()
    width = 1 << max(longest - 1, 1).bit_length()
    sidx = SpladeIndex.load(index_dir / "splade", mmap=True)
    if s["method"] == "colbert":
        plaid = PlaidParams(
            nprobe=s["nprobe"],
            candidate_cap=cfg["corpus"]["query_maxlen"] * s["nprobe"] * width,
            ndocs=harness.survivors(s))
    else:
        plaid = PlaidParams()          # hybrid never probes
    ms = MultiStageParams(**{k: s[k] for k in ("first_k", "alpha")
                             if k in s})
    searcher = PLAIDSearcher(index, plaid, ivf_pad=width)
    return MultiStageRetriever(sidx, searcher, ms), longest


def go_live(retr, cfg: dict):
    """Attach the live index where the configuration has
    ``serving.live``, as ``launch/serve.py --live --live-compact-every
    N`` does → its ``AutoCompactor``, not yet started; None for a
    frozen index."""
    live = cfg["serving"].get("live")
    if live is None:
        return None
    from repro.index.live import AutoCompactor
    retr.enable_live()
    return AutoCompactor(retr, live["compact_every"])


def stop_compactor(compactor):
    """Stop the program's ``AutoCompactor`` and wait until its thread
    has ended; a compaction under way runs to its end. Its ``stop()``
    joins through ``threading.Thread``, and on Python 3.12 that join
    raises TypeError as soon as it finds the thread ended: the
    compactor's stop event is named ``_stop``, which shadows the method
    ``Thread`` calls there (PERF.md §7). That TypeError is the sign
    that the thread has ended."""
    while True:
        try:
            compactor.stop()
            if not compactor.is_alive():
                return
        except TypeError:
            return


def warm(retr, cfg: dict, k: int, upserted: dict | None = None):
    """Compile (or load from the cache) every program the window can
    dispatch: the cell's method at each micro-batch size 1..max_batch,
    on queries of the served shape. Queries take the terms of the
    ``upserted`` passages (``term_ids``, ``term_weights``) where given,
    so that the live overlay path scores their delta rows too."""
    c, s = cfg["corpus"], cfg["serving"]
    rng = np.random.default_rng(0)
    b_max = s["max_batch"]
    q = rng.standard_normal((b_max, c["query_maxlen"], c["dim"]),
                            dtype=np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    if upserted is None:
        terms = rng.integers(0, c["vocab"], (b_max, c["query_nnz"]),
                             dtype=np.int32)
        weights = np.ones((b_max, c["query_nnz"]), np.float32)
    else:
        rows = np.arange(b_max) % len(upserted["term_ids"])
        terms = np.asarray(upserted["term_ids"], np.int32)[
            rows, :c["query_nnz"]]
        weights = np.asarray(upserted["term_weights"], np.float32)[
            rows, :c["query_nnz"]]
    for b in range(1, b_max + 1):
        retr.search_batch(s["method"], q_embs=list(q[:b]),
                          term_ids=list(terms[:b]),
                          term_weights=list(weights[:b]), k=k)


def rss() -> dict:
    """This process's resident memory by kind, in bytes: ``VmRSS`` and,
    where the kernel reports them, ``RssAnon``/``RssFile``/``RssShmem``.
    Where ``RssAnon`` is missing (a sandboxed kernel), ``Anonymous`` is
    the sum of the ``Anonymous:`` lines of ``/proc/self/smaps``."""
    out = {}
    with open("/proc/self/status") as f:
        for line in f:
            key = line.split(":")[0]
            if key in ("VmRSS", "RssAnon", "RssFile", "RssShmem"):
                out[key] = int(line.split()[1]) * 1024
    try:
        with open("/proc/self/smaps") as f:
            anon = sum(int(line.split()[1]) for line in f
                       if line.startswith("Anonymous:"))
        out["Anonymous"] = anon * 1024
    except OSError:
        pass
    return out


def anon_bytes(mem: dict) -> int:
    """Anonymous resident memory: what a deployment must provision; the
    evictable file pages of the mmap'd index are left out."""
    return mem["RssAnon"] if "RssAnon" in mem else mem["Anonymous"]


class Recorder:
    """Server-side record of the window: each answered request's
    arrival, start and finish (the program's own ``Result`` stamps), and
    snapshots of the plan's per-stage statistics."""

    def __init__(self, server, retr):
        self.results = []
        self.snapshots = {}
        self._retr = retr
        self._lock = threading.Lock()
        submit = server.submit

        def recorded(req):
            fut = submit(req)
            fut.add_done_callback(self._done)
            return fut
        server.submit = recorded

    def _done(self, fut):
        if fut.exception() is None:
            r = fut.result()
            with self._lock:
                self.results.append((r.t_arrival, r.t_start, r.t_done))

    def snapshot(self, name):
        self.snapshots[name] = self._retr.pipeline_stats.snapshot()


def _at(t, fn):
    def run():
        time.sleep(max(0.0, t - time.monotonic()))
        fn()
    th = threading.Thread(target=run, daemon=True)
    th.start()
    return th


def preload(client, port: int) -> dict:
    """Have the load generator send the traffic's ``preload`` updates
    → the terms of the new versions."""
    client.stdin.write(f"PRELOAD {port}\n")
    client.stdin.flush()
    line = client.stdout.readline().split(maxsplit=1)
    if not line or line[0] != "PRELOADED":
        raise RuntimeError("the load generator did not preload")
    return json.loads(line[1])


def serve_window(c: dict, seed: int, seconds: float, trace: bool,
                 index_dir, work, client) -> dict:
    """Set-up step 3, the window and the shutdown → the run's record."""
    import jax

    from repro.serving.engine import ServeEngine
    from repro.serving.server import RetrievalServer

    cfg, traffic = c["config"], c["traffic"]
    s = cfg["serving"]
    phases = {"jax_ready": time.monotonic() - T_START}
    retr, longest = open_retriever(cfg, index_dir)
    compactor = go_live(retr, cfg)
    phases["index_open"] = time.monotonic() - T_START
    if compactor is None:
        warm(retr, cfg, traffic["k"])
        phases["warm"] = time.monotonic() - T_START
        retr.reset_stage_stats()
    engine = ServeEngine(retr, pipeline_depth=s["pipeline_depth"])
    server = RetrievalServer(engine, max_batch=s["max_batch"],
                             batch_timeout_ms=s["batch_timeout_ms"])
    recorder = Recorder(server, retr)
    server.start()
    tcp = server.serve_tcp("127.0.0.1", 0)
    loop = threading.Thread(target=tcp.serve_forever, daemon=True)
    loop.start()
    ready = client.stdout.readline().split()
    if not ready or ready[0] != "READY":
        raise RuntimeError("the load generator did not start")
    phases["client_ready"] = time.monotonic() - T_START
    if compactor is not None:
        warm(retr, cfg, traffic["k"], preload(client, server.tcp_port))
        phases["preload_and_warm"] = time.monotonic() - T_START
        retr.reset_stage_stats()
        compactor.start()
    rec = {"trace": None, "tail_calls": [], "interval": (0.0, seconds),
           "phases": phases, "ivf_longest": longest}
    undo = None
    t0 = time.monotonic() + 0.5
    clock = time.perf_counter() - time.monotonic()   # perf_counter − mono
    rec["setup_s"] = t0 - T_START
    if trace:
        import tracing
        undo, tail_calls = tracing.instrument(cfg)
        a, b = tracing.window(seconds)
        tdir = work / "trace"
        marks = {}

        def begin():
            recorder.snapshot("begin")
            marks["a"] = time.monotonic() - t0
            jax.profiler.start_trace(str(tdir))

        def end():
            marks["b"] = time.monotonic() - t0
            recorder.snapshot("end")
            jax.profiler.stop_trace()
        threads = [_at(t0 + a, begin), _at(t0 + b, end)]
    mem, live = {}, {}

    def close():
        mem.update(rss())
        recorder.snapshot("close")
        live["close"] = retr.live_stats()
    at_close = _at(t0 + seconds, close)
    recorder.snapshot("start")
    live["start"] = retr.live_stats()
    client.stdin.write(f"GO {server.tcp_port} {t0!r}\n")
    client.stdin.flush()
    client.wait(timeout=seconds + CLIENT_GRACE_S)
    if client.returncode != 0:
        raise RuntimeError(f"load generator failed (exit "
                           f"{client.returncode})")
    at_close.join()
    if trace:
        for th in threads:
            th.join()
        rec["interval"] = (marks["a"], marks["b"])
    if compactor is not None:
        stop_compactor(compactor)
    server.shutdown_gracefully()
    tcp.server_close()
    loop.join(timeout=10)
    engine.close()
    if undo is not None:
        undo()
        rec["tail_calls"] = [
            x for x in tail_calls
            if rec["interval"][0] <= x["t"] - clock - t0
            <= rec["interval"][1]]
    devices = jax.devices()[:c["workload"]["chips"]]
    rec["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices)
    rec["device"] = harness.device_info(jax.devices())
    rec["rss"] = mem
    rec["results"] = np.array(recorder.results).reshape(-1, 3) - clock - t0
    rec["stages"] = recorder.snapshots
    rec["live"] = live
    rec["first_stage"] = retr.compile_plan(s["method"]).stages[0].name
    if trace:
        import trace as trace_mod
        rec["trace"] = trace_mod.reduce_dir(work / "trace",
                                            cfg["tail_program"])
    del retr, engine, server, tcp
    gc.collect()
    return rec


def read_metric(name: str, rec: dict):
    """The per-layer metric ``name``, from its reader
    ``bench/metrics/<name>.py``; None where it finds nothing to read."""
    return layers.reader(name)(rec)


def _for_cell(metrics, cell: str):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def end_to_end(bench: dict, cell: str, rec: dict, seconds: float) -> dict:
    client = rec["client"]
    values = {"setup_s": rec["setup_s"],
              "p50_ms": stats.percentile_ms(client, 50),
              "p95_ms": stats.percentile_ms(client, 95),
              "qps": stats.qps(client, seconds),
              "host_ram_mb": anon_bytes(rec["rss"]) / 1e6}
    return {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in _for_cell(bench["end_to_end"], cell)}


def per_layer(bench: dict, cell: str, rec: dict) -> dict:
    out = {}
    for m in _for_cell(bench["per_layer"], cell):
        v = read_metric(m["name"], rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def run(workload: str, seed: int, seconds: float, trace: bool,
        require_tpu: bool = True, c: dict | None = None,
        bench: dict | None = None, forget_writes: bool = False) -> dict:
    """One run of a cell → the result object (without printing it).
    ``c`` and ``bench`` stand in for the cell and ``BENCHMARK.json``
    that the workload's name finds; ``require_tpu=False`` lets a CPU
    rehearsal drive everything but the look for a chip.
    ``forget_writes`` judges the answers against the corpus as it was
    before any write: the control of the check under writes."""
    c = c or harness.cell(workload)
    bench = bench or harness.benchmark()
    harness.use_compile_cache()
    work = harness.WORK / workload
    shutil.rmtree(work, ignore_errors=True)
    work.mkdir(parents=True)
    index_dir = work / "index"
    build = build_index(c, seed, index_dir, require_tpu)
    build["times"]["done_at"] = time.monotonic() - T_START
    client = subprocess.Popen(
        [sys.executable, str(harness.HERE / "client.py"), "--config",
         str(c["config_file"]), "--traffic", str(c["traffic_file"]),
         "--seed", str(seed), "--seconds", repr(seconds), "--out",
         str(work / "client.npz")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
        cwd=harness.ROOT)
    try:
        import jax
        _check_device(harness.device_info(jax.devices()),
                      c["workload"]["chips"], require_tpu)
        rec = serve_window(c, seed, seconds, trace, index_dir, work, client)
    finally:
        if client.poll() is None:
            client.kill()
            client.wait()
    with np.load(work / "client.npz") as z:
        rec["client"] = {k: z[k] for k in z.files}
    rec.update(config=c["config"], traffic=c["traffic"],
               workload=c["workload"], seconds=seconds,
               build=build["times"])
    if trace:
        metrics = per_layer(bench, workload, rec)
    else:
        metrics = end_to_end(bench, workload, rec, seconds)
    chk = c["config"]["check"]
    sample = stats.sample(rec["client"], chk["sample"], seed)
    t0 = time.monotonic()
    numbers = reference.check(c["config"], c["traffic"], index_dir, seed,
                              rec["client"], sample, forget_writes)
    ref_s = time.monotonic() - t0
    limits = chk["limits"]
    correct = all(numbers[n] <= lim for n, lim in limits.items())
    checks = {n: {"value": numbers[n], "limit": lim}
              for n, lim in limits.items()}
    device = dict(rec["device"], memory_peak_bytes=rec["memory_peak_bytes"])
    if trace and rec["trace"] is not None:
        device.update(busy_s=rec["trace"]["busy_s"],
                      window_s=rec["trace"]["window_s"])
    cl = rec["client"]
    result = {"correct": bool(correct),
              "attempted": int(len(cl["status"])
                               + len(cl.get("w_status", ()))),
              "failed": stats.failed(cl),
              "metrics": metrics, "device": device}
    if trace and rec["trace"] is not None:
        result["breakdown"] = rec["trace"]["breakdown"]
    counters = [rec["stages"][x]["counters"] for x in ("start", "close")]
    result["info"] = {"build_s": build["times"], "reference_s": ref_s,
                      "setup_phases_s": rec["phases"],
                      "ivf_longest_list": rec["ivf_longest"],
                      "rss_at_close": rec["rss"], "sampled": len(sample),
                      "ambiguous": numbers["ambiguous"],
                      "in_flight_at_close": stats.in_flight(cl, seconds),
                      "jax_compiles_in_window": (
                          counters[1].get("jax_compiles", 0)
                          - counters[0].get("jax_compiles", 0))}
    if "w_status" in cl:
        lv = rec["live"]
        result["info"]["writes"] = {
            "sent": int(len(cl["w_status"])),
            "acked": stats.writes_acked(cl),
            "compactions_in_window": (lv["close"].get("compactions", 0)
                                      - lv["start"].get("compactions", 0)),
            "states_judged": numbers["states"]}
    result["checks"] = checks
    shutil.rmtree(index_dir, ignore_errors=True)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace))
    except NoChip as e:
        print(f"bench/run.py: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
