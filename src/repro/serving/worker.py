"""Shard worker process: one shard's stage plan behind a socket RPC.

    python -m repro.serving.worker --shard-dir <base>/shards/0 \
        [--fd N | --port 0] [--mode mmap] [--shard-index 0] \
        [--transport socket|shm] [--arena /dev/shm/….arena] \
        [--plaid-json '{...}'] [--ms-json '{...}']

Each worker is a **shared-nothing** serving process: it loads only its
own ``shards/<i>/{colbert,splade}`` subtree — its own mmap
:class:`PagedStore` segment (independent page cache working set), its
own SPLADE postings slice (and device cache when a device backend is
selected), and its own Python interpreter (independent GIL). The
coordinator (:class:`repro.core.sharded.ProcessShardGroup`) ships
shard slices of the batch over ``repro.serving.rpc`` and merges the
returned scores with the same ``merge_topk`` the in-process shard
group uses, so process-group results are bitwise-identical to thread
workers (and therefore to ``shards=1``).

Exposed ops (each mirrors one per-shard stage of the sharded plans;
inputs and the underlying stage functions are exactly the in-process
ones, which is the parity argument):

* ``ping`` / ``health``          — readiness + vitals (pid, RSS, mmap
  segment bytes, served count)
* ``warm {backend}``             — pre-materialise the SPLADE device
  cache for a device stage-1 backend
* ``splade``                     — shard-local stage-1 top-k
* ``score_tokens``               — compacted-candidate residual gather
  + exact MaxSim (rerank/hybrid stage 3–4)
* ``colbert_candidates``         — IVF candidate gen + codes gather +
  approximate scoring (PLAID stages 2–3)
* ``colbert_exact``              — survivor residual gather + exact
  scoring (PLAID stage 4)
* ``multi {ops: […]}``           — coalesced sub-ops (one dispatch per
  worker per stage); one reply with a per-op ok/error slot each
* ``shutdown``                   — reply, then exit 0

Lifecycle: SIGTERM requests a **graceful drain** — the op in flight
finishes and its reply is sent before the process exits 0, so a batch
never loses a shard's answer to a routine redeploy; SIGKILL (crash) is
detected by the coordinator as EOF and surfaces as ``ShardWorkerDied``.
The worker serves one request at a time; concurrency comes from the
coordinator running one worker per shard (and pipelining at most one
outstanding request per in-flight micro-batch).
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import socket
import sys
import time

import numpy as np

# jax imports are deferred to main() on purpose: the coordinator treats
# the first ping reply as the readiness barrier, and everything heavy
# (jax init, index mmap) must happen before that reply, not lazily
# inside the first scoring op.


class _WorkerState:
    def __init__(self, retriever, shard_index: int):
        self.retr = retriever
        self.shard = shard_index
        self.served = 0
        self.t_start = time.monotonic()
        self.draining = False
        self.channel = None            # set by serve_connection


def _rss_bytes() -> int:
    from repro.core.store import rss_bytes
    return rss_bytes()


def _handle(state: _WorkerState, op: str, payload: dict):
    import jax.numpy as jnp

    from repro.common.utils import next_pow2
    from repro.core.plaid import (
        stage2_candidates_batch,
        stage3_approx_score_batch,
    )

    retr = state.retr
    sr = retr.searcher

    if op == "ping":
        return {"pid": os.getpid(), "shard": state.shard,
                "ready": True}

    if op == "health":
        h = {"pid": os.getpid(), "shard": state.shard,
             "rss_bytes": _rss_bytes(),
             "pool_bytes": sr.index.store.total_bytes(),
             "n_docs": retr.splade.n_docs,
             "served": state.served,
             "uptime_s": time.monotonic() - state.t_start,
             "access": sr.index.store.stats.snapshot()}
        if retr.live is not None:
            h["live"] = retr.live.stats()
            h["generation"] = retr.index_generation
        if state.channel is not None:
            # worker-side view of the same channel (its bytes_sent is
            # the coordinator's bytes_recv); keyed distinctly so it
            # never clobbers the coordinator's transport fields
            h["worker_transport"] = state.channel.stats()
        return h

    if op == "warm":
        backend = payload.get("backend", "host")
        retr.set_splade_backend(backend)
        if backend != "host":
            retr.splade_device_cache()
        return {"warmed": backend}

    if op == "splade":
        # identical call to the thread-mode group stage: shard-local
        # postings, shard-local top-k; the coordinator remaps to global
        # pids and merge_topk's the group
        pids, scores = retr.run_splade_batch(
            list(payload["term_ids"]), list(payload["term_weights"]),
            int(payload["k"]), backend=payload.get("backend"),
            _record=False)
        return {"pids": pids, "scores": scores}

    if op == "score_tokens":
        # rerank/hybrid stages 3-4 for this shard's compacted slice:
        # mmap residual gather + exact MaxSim, synced before the reply
        # (no lazy device values cross a process boundary)
        sel = payload["sel"]
        codes, packed, valid = sr._dedup_gather(sel, codes_only=False)
        scores = np.asarray(sr.score_gathered_lazy(
            jnp.asarray(payload["q"]), jnp.asarray(payload["q_valid"]),
            jnp.asarray(codes), jnp.asarray(packed), jnp.asarray(valid),
            sel))
        return {"scores": scores}

    if op == "colbert_candidates":
        # PLAID stages 2-3 over this shard's IVF slice; the candidate
        # matrix narrows to the densest row's pow2 bucket exactly like
        # the in-process fanout stage, and raw approx scores go back
        # unsorted — survivor selection stays global on the coordinator
        cand = stage2_candidates_batch(
            sr.ivf_padded, jnp.asarray(payload["cids"]),
            sr.params.candidate_cap)
        cand_np = np.asarray(cand)
        n_real = (cand_np >= 0).sum(axis=1)
        W = min(next_pow2(max(int(n_real.max()), 8)), cand_np.shape[1])
        cand, cand_np = cand[:, :W], cand_np[:, :W]
        codes, _, valid = sr._dedup_gather(cand_np, codes_only=True)
        approx = stage3_approx_score_batch(
            jnp.asarray(payload["scores_c"]), jnp.asarray(codes),
            jnp.asarray(valid), jnp.asarray(payload["q_valid"]))
        approx = jnp.where(cand >= 0, approx, -jnp.inf)
        return {"cand": cand_np, "approx": np.asarray(approx),
                "n_real": n_real}

    if op == "colbert_exact":
        sel = payload["sel"]
        codes, packed, valid = sr._dedup_gather(sel, codes_only=False)
        exact = sr.exact_score_gathered(
            jnp.asarray(payload["q"]), jnp.asarray(payload["q_valid"]),
            jnp.asarray(codes), jnp.asarray(packed), jnp.asarray(valid),
            jnp.asarray(sel))
        return {"scores": np.asarray(exact)}

    if op == "live_sync":
        # full-state tombstone replication (idempotent): the worker's
        # SPLADE stage excludes these local pids pre-top-k, exactly
        # like the in-process thread shards' LiveViews
        from repro.index.live import LiveView

        if retr.live is None:
            retr.live = LiveView()
        retr.live.update(payload.get("tombstones"),
                         generation=payload.get("generation"))
        retr.index_generation = int(payload.get("generation") or 0)
        return {"tombstones": int(retr.live.tombstones.size),
                "generation": retr.live.generation}

    if op == "live_reload":
        # compaction swap: rebuild index/searcher handles from the new
        # generation's directories and reset the tombstone view to the
        # shard's (grown) range
        import pathlib

        from repro.core.plaid import PLAIDSearcher
        from repro.index.builder import ColBERTIndex
        from repro.index.live import LiveView
        from repro.index.splade_index import SpladeIndex

        mode = sr.index.store.mode
        index = ColBERTIndex(pathlib.Path(payload["colbert_dir"]),
                             mode=mode)
        sidx = SpladeIndex.load(pathlib.Path(payload["splade_dir"]),
                                mmap=(mode == "mmap"))
        retr.splade = sidx
        retr.searcher = PLAIDSearcher(index, sr.params)
        with retr._lock:
            retr._plans.clear()
            retr._splade_device = None
        retr.live = LiveView(payload.get("tombstones"),
                             generation=payload.get("generation") or 0)
        retr.index_generation = int(payload.get("generation") or 0)
        return {"n_docs": int(sidx.n_docs),
                "generation": retr.index_generation}

    raise ValueError(f"unknown RPC op {op!r}")


def _run_op(state: _WorkerState, op: str, payload) -> dict:
    """One op → one ``{"ok": …}`` reply dict; compute errors are
    reported, never fatal."""
    try:
        result = _handle(state, op, payload or {})
        state.served += 1
        return {"ok": True, "result": result}
    except Exception:                    # compute error ≠ worker death
        import traceback
        return {"ok": False, "error": traceback.format_exc()}


def serve_connection(channel, state: _WorkerState):
    """Request loop: one op at a time, FIFO replies, per-op errors
    reported (never fatal), SIGTERM drained between ops.

    A ``multi`` op carries a list of coalesced sub-ops (one coordinator
    dispatch per worker per stage); each sub-op gets its own ok/error
    slot in the single reply, so one bad micro-batch never poisons its
    co-batched neighbours."""
    state.channel = channel
    channel.sock.setblocking(True)
    while not state.draining:
        try:
            # the channel's pump (not a socket timeout) paces the drain
            # poll: partial frames persist in its buffer across slices,
            # and frames already buffered decode without touching the
            # socket — a select-gated loop would strand them
            msg = channel.pump(0.5)
        except (ConnectionError, OSError):
            return                       # coordinator went away
        except ValueError:
            # undecodable frame: the stream is desynced (a corrupted or
            # torn write on the client side). That is the *connection's*
            # problem, never the worker's — drop the connection and let
            # the accept loop serve the next one; a standalone fleet
            # worker must survive any bytes a client throws at it
            return
        if msg is None:
            continue
        op = msg.get("op", "")
        if op == "multi":
            ops = (msg.get("payload") or {}).get("ops") or []
            reply = {"ok": True, "result": {
                "replies": [_run_op(state, sub.get("op", ""),
                                    sub.get("payload")) for sub in ops]}}
        else:
            reply = _run_op(state, op, msg.get("payload"))
        try:
            channel.send(reply)
        except (ConnectionError, OSError):
            return
        if op == "shutdown":
            return


def spawn_standalone(shard_dir, shard_index: int = 0, *,
                     mode: str = "mmap", port: int = 0,
                     plaid_params=None, ms_params=None,
                     timeout_s: float = 180.0):
    """Spawn a standalone worker subprocess (``--port`` mode) and wait
    for its ``RPC_PORT=<n>`` readiness line; returns ``(proc, port)``.

    The fleet harness behind remote-replica tests, the chaos smoke and
    ``bench_latency.py --chaos-sweep``: each call stands up one
    independently killable/restartable worker a coordinator attaches
    to via ``replica_endpoints=…``. ``port=0`` binds an ephemeral
    port; pass the old port back in to restart a killed worker at the
    same endpoint (the listener sets SO_REUSEADDR)."""
    import subprocess

    from repro.serving.transport.client import _src_pythonpath

    cmd = [sys.executable, "-m", "repro.serving.worker",
           "--shard-dir", str(shard_dir),
           "--shard-index", str(shard_index),
           "--mode", mode, "--port", str(port),
           "--plaid-json", json.dumps(plaid_params or {}),
           "--ms-json", json.dumps(ms_params or {})]
    from repro.launch.mesh import shard_worker_env

    env = shard_worker_env(1)
    env["PYTHONPATH"] = _src_pythonpath()
    proc = subprocess.Popen(cmd, env=env, stdin=subprocess.DEVNULL,
                            stdout=subprocess.PIPE, text=True)
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        line = proc.stdout.readline()
        if not line:
            break                       # EOF: the worker died
        if line.startswith("RPC_PORT="):
            return proc, int(line.strip().split("=", 1)[1])
    proc.kill()
    proc.wait(timeout=10)
    raise RuntimeError(
        f"standalone worker for shard {shard_index} ({shard_dir}) "
        f"never reported RPC_PORT= (exit code {proc.returncode})")


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shard-dir", required=True,
                    help="this shard's subtree: <dir>/{colbert,splade}")
    ap.add_argument("--shard-index", type=int, default=0)
    ap.add_argument("--mode", default="mmap", choices=["mmap", "ram"])
    ap.add_argument("--fd", type=int, default=None,
                    help="inherited socketpair fd (coordinator-spawned)")
    ap.add_argument("--port", type=int, default=None,
                    help="standalone mode: listen on 127.0.0.1:PORT "
                         "(0 = ephemeral; prints RPC_PORT=<n>)")
    ap.add_argument("--transport", default="socket",
                    choices=["socket", "shm"],
                    help="tensor transport: in-frame socket segments "
                         "or a shared-memory ring arena")
    ap.add_argument("--arena", default=None,
                    help="arena file created by the coordinator "
                         "(required for --transport shm)")
    ap.add_argument("--plaid-json", default="{}")
    ap.add_argument("--ms-json", default="{}")
    args = ap.parse_args(argv)
    if (args.fd is None) == (args.port is None):
        ap.error("exactly one of --fd / --port is required")
    if args.transport == "shm" and args.arena is None:
        ap.error("--transport shm requires --arena")
    if args.transport == "shm" and args.port is not None:
        ap.error("--transport shm requires --fd (coordinator-spawned)")

    # heavy imports after arg validation; the parent's first ping blocks
    # until this completes
    import pathlib

    from repro.core.multistage import MultiStageParams, MultiStageRetriever
    from repro.core.plaid import PLAIDSearcher, PlaidParams
    from repro.index.builder import ColBERTIndex
    from repro.index.splade_index import SpladeIndex
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    d = pathlib.Path(args.shard_dir)
    index = ColBERTIndex(d / "colbert", mode=args.mode)
    sidx = SpladeIndex.load(d / "splade", mmap=(args.mode == "mmap"))
    retr = MultiStageRetriever(
        sidx, PLAIDSearcher(index, PlaidParams(**json.loads(args.plaid_json))),
        MultiStageParams(**json.loads(args.ms_json)))
    state = _WorkerState(retr, args.shard_index)

    def on_sigterm(signum, frame):
        # graceful drain: finish (and answer) the op in flight, then
        # exit — the loop checks the flag between requests
        state.draining = True

    signal.signal(signal.SIGTERM, on_sigterm)

    from repro.serving.transport import (RING_C2W, RING_W2C, ShmArena,
                                         ShmChannel, StreamChannel)

    if args.fd is not None:
        sock = socket.socket(fileno=args.fd)
        if args.transport == "shm":
            arena = ShmArena.open(args.arena)

            def coordinator_gone():
                # producer-side liveness while blocked on reply-ring
                # space: a closed socket (EOF visible via MSG_PEEK)
                # means the coordinator is gone — bail, don't wedge
                try:
                    data = sock.recv(1, socket.MSG_PEEK
                                     | socket.MSG_DONTWAIT)
                except (BlockingIOError, InterruptedError):
                    return None
                except OSError as e:
                    return f"socket error ({e})"
                return (None if data
                        else "coordinator closed the connection")

            channel = ShmChannel(sock, arena, tx_ring=RING_W2C,
                                 rx_ring=RING_C2W,
                                 liveness=coordinator_gone)
        else:
            channel = StreamChannel(sock)
        try:
            serve_connection(channel, state)
        finally:
            channel.close()
        return 0

    srv = socket.create_server(("127.0.0.1", args.port))
    srv.settimeout(0.5)
    print(f"RPC_PORT={srv.getsockname()[1]}", flush=True)
    try:
        while not state.draining:
            try:
                conn, _ = srv.accept()
            except socket.timeout:
                continue
            with conn:
                serve_connection(StreamChannel(conn), state)
    finally:
        srv.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
