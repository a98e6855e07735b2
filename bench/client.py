"""Open-loop load generator: a child process with NumPy and sockets only.

    python bench/client.py --config <file> --traffic <file> --seed <n>
        --seconds <s> --out <file.npz>

It draws the cell's queries and arrival schedule from the seed, encodes
every request as the newline-JSON the TCP front takes, and prints
``READY``. It then reads ``GO <port> <t0>`` from standard input, ``t0``
on the monotonic clock the server process shares, opens a pool of
persistent connections to the TCP front, and sends each request at its
due time ``t0 + due`` on an idle connection, opening another where none
is idle, so that every due request is in flight whatever the server's
backlog: a connection carries one request at a time, as the front
serves it. After the last send it waits for the outstanding replies, at
most ``--drain`` seconds past the window. Each request's due, send and reply times (seconds from
``t0``; NaN where no reply came), its status (0 answered, 1 error reply,
2 no reply) and its answer go to ``--out``.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import selectors
import socket
import sys
import time
from collections import deque

import numpy as np

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import gen  # noqa: E402
import harness  # noqa: E402

OK, ERROR, NO_REPLY = 0, 1, 2
POOL = 64


def encode_requests(cfg: dict, traffic: dict, seed: int, n: int):
    """The cell's first ``n`` requests, encoded."""
    corpus = harness.corpus(cfg)
    docs = gen.make_corpus(corpus, seed)
    q = gen.make_queries(corpus, docs, n, seed)
    method, k = cfg["serving"]["method"], traffic["k"]
    out = []
    for i in range(n):
        msg = {"qid": i, "method": method, "k": k,
               "q_emb": q["q_embs"][i].tolist(),
               "term_ids": q["q_term_ids"][i].tolist(),
               "term_weights": q["q_term_weights"][i].tolist()}
        out.append((json.dumps(msg) + "\n").encode())
    return out


def schedule(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Due times of the window's requests: one arrival per burst, each
    bringing ``burst`` requests at once."""
    n = harness.n_requests(traffic, seconds)
    t = gen.arrivals(n // traffic["burst"], seconds, seed)
    return np.repeat(t, traffic["burst"])


class Conn:
    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.out = memoryview(b"")
        self.inbuf = bytearray()
        self.req = -1


def drive(reqs, due, port: int, t0: float, drain_until: float, k: int):
    """Send ``reqs[i]`` at ``t0 + due[i]``; → per-request send and reply
    times (monotonic), status, pids and scores."""
    n = len(reqs)
    sent = np.full(n, np.nan)
    done = np.full(n, np.nan)
    status = np.full(n, NO_REPLY, np.int8)
    pids = np.full((n, k), -2, np.int64)
    scores = np.full((n, k), np.nan)
    sel = selectors.DefaultSelector()
    idle = deque()
    for _ in range(min(POOL, n)):
        c = Conn(port)
        sel.register(c.sock, selectors.EVENT_READ, c)
        idle.append(c)
    due_abs = t0 + np.asarray(due)
    i = inflight = 0
    while True:
        now = time.monotonic()
        while i < n and due_abs[i] <= now:
            sent[i] = now
            try:
                if idle:
                    c = idle.popleft()
                else:
                    c = Conn(port)
                    sel.register(c.sock, selectors.EVENT_READ, c)
                c.req, c.out = i, memoryview(reqs[i])
                _send(sel, c)
                inflight += 1
            except OSError:          # a refused connection fails it
                status[i], done[i] = ERROR, time.monotonic()
            i += 1
            now = time.monotonic()
        if i == n and (inflight == 0 or now >= drain_until):
            break
        wait = (due_abs[i] if i < n else drain_until) - now
        for key, mask in sel.select(timeout=max(0.0, wait)):
            c = key.data
            try:
                if mask & selectors.EVENT_WRITE:
                    _send(sel, c)
                chunk = (c.sock.recv(1 << 20) if mask & selectors.EVENT_READ
                         else None)
            except BlockingIOError:
                continue
            except OSError:
                chunk = b""
            if chunk == b"":         # the server dropped the connection
                sel.unregister(c.sock)
                c.sock.close()
                if c.req >= 0 and status[c.req] == NO_REPLY:
                    status[c.req], done[c.req] = ERROR, time.monotonic()
                    inflight -= 1
                continue
            if chunk:
                c.inbuf += chunk
                if c.inbuf.endswith(b"\n"):
                    j = c.req
                    reply = json.loads(c.inbuf)
                    done[j] = time.monotonic()
                    c.inbuf.clear()
                    if "error" in reply or reply.get("qid") != j:
                        status[j] = ERROR
                    else:
                        status[j] = OK
                        m = min(k, len(reply["pids"]))
                        pids[j, :m] = reply["pids"][:m]
                        scores[j, :m] = reply["scores"][:m]
                    inflight -= 1
                    c.req = -1
                    idle.append(c)
    for key in list(sel.get_map().values()):
        key.data.sock.close()
    sel.close()
    return sent, done, status, pids, scores


def _send(sel, c: Conn):
    if len(c.out):
        try:
            c.out = c.out[c.sock.send(c.out):]
        except BlockingIOError:
            pass
    sel.modify(c.sock, selectors.EVENT_READ
               | (selectors.EVENT_WRITE if len(c.out) else 0), c)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--drain", type=float, default=60.0)
    args = ap.parse_args(argv)
    cfg = harness.load_json(args.config)
    traffic = harness.load_json(args.traffic)
    due = schedule(traffic, args.seconds, args.seed)
    reqs = encode_requests(cfg, traffic, args.seed, len(due))
    print(f"READY {len(reqs)}", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 3 or line[0] != "GO":
        raise SystemExit("client: no GO from the server process")
    port, t0 = int(line[1]), float(line[2])
    sent, done, status, pids, scores = drive(
        reqs, due, port, t0, t0 + args.seconds + args.drain,
        traffic["k"])
    np.savez(args.out, due=due, sent=sent - t0, done=done - t0,
             status=status, pids=pids, scores=scores)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
