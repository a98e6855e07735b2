"""Open-loop load generator: a child process with NumPy and sockets only.

    python bench/client.py --config <file> --traffic <file> --seed <n>
        --seconds <s> --out <file.npz> [--keys <file.npy>]

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

Traffic with a ``writes`` key mixes updates into the same schedule: a
seeded ``share`` of the arrivals are writes. An update of a key is a new
version of its passage, sent as ``{"op": "upsert"}`` and then
``{"op": "delete"}`` of the key's current pid, which the client tracks
from the upsert replies. Writes go in order on one writer connection,
one at a time, each once it is due and the one before it is
acknowledged. Before ``GO``, a ``PRELOAD <port>`` line has the first
``preload`` updates sent the same way, and is answered with
``PRELOADED`` and the terms of those new versions. Each write's due,
send and acknowledgement times, status, operation, key, pid (assigned
or deleted) and version go to ``--out`` in arrays of their own
(``w_*``); the query arrays hold queries only. ``--keys`` names a file
that holds each key's current pid, read at the start where it exists
and written at the end, so that windows served one after another by the
same index (``sweep.py``) continue from each other's writes.
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
UPSERT, DELETE = 0, 1
POOL = 64


def encode_requests(cfg: dict, traffic: dict, seed: int, n: int,
                    docs: dict | None = None):
    """The cell's first ``n`` queries, encoded."""
    corpus = harness.corpus(cfg)
    if docs is None:
        docs = gen.make_corpus(corpus, seed)
    q = gen.make_queries(corpus, docs, n, seed,
                         gen.query_rel(corpus, traffic, n, seed))
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


def split(traffic: dict, due: np.ndarray, seed: int):
    """→ (due times of the queries, due times of the window's updates)."""
    w = traffic.get("writes")
    if w is None:
        return due, due[:0]
    if w["op"] != "update":
        raise ValueError(f"unknown write op {w['op']!r}")
    is_write = gen.write_slots(len(due), w["share"], seed)
    return due[~is_write], due[is_write]


def encode_updates(cfg: dict, traffic: dict, seed: int, n: int,
                   docs: dict):
    """The set-up's ``preload`` updates and the window's ``n``, in order
    → (keys, versions, the upsert line of each new version)."""
    corpus = harness.corpus(cfg)
    w = traffic["writes"]
    keys = gen.write_keys(corpus, w, w.get("preload", 0) + n, seed)
    v = gen.make_versions(corpus, docs, keys, seed)
    lines = []
    for j in range(len(keys)):
        msg = {"op": "upsert",
               "doc_emb": v["embs"][j, :v["lens"][j]].tolist(),
               "term_ids": v["term_ids"][j].tolist(),
               "term_weights": v["term_weights"][j].tolist()}
        lines.append((json.dumps(msg) + "\n").encode())
    return keys, v, lines


class Writer:
    """The update stream: each update an upsert then a delete, sent in
    order, one at a time, each once due and the one before acknowledged.
    Keeps the record of every operation and each key's current pid."""

    def __init__(self, keys, versions, lines, due, key_pid):
        self.lines, self.key_pid = lines, key_pid
        n = 2 * len(keys)
        self.due = np.repeat(np.asarray(due, float), 2)
        self.sent = np.full(n, np.nan)
        self.ack = np.full(n, np.nan)
        self.status = np.full(n, NO_REPLY, np.int8)
        self.op = np.tile(np.array([UPSERT, DELETE], np.int8), len(keys))
        self.key = np.repeat(np.asarray(keys, np.int64), 2)
        self.pid = np.full(n, -1, np.int64)
        self.version = np.repeat(np.asarray(versions, np.int64), 2)
        self.version[1::2] = -1
        self.i = 0                  # the next operation
        self.busy = False

    def next_due(self):
        """Due time (s from ``t0``) of the next operation; None while
        one is in flight or when none is left."""
        return None if self.busy or self.i >= len(self.due) else \
            self.due[self.i]

    def take(self, now: float) -> bytes:
        """The next operation's line; marks it sent at ``now``."""
        i = self.i
        self.sent[i], self.busy = now, True
        if self.op[i] == UPSERT:
            return self.lines[self.version[i]]
        self.pid[i] = self.key_pid[self.key[i]]
        return (json.dumps({"op": "delete", "pid": int(self.pid[i])})
                + "\n").encode()

    def reply(self, now: float, reply: dict | None):
        """Record the in-flight operation's reply (None: no reply)."""
        i = self.i
        self.ack[i], self.busy = now, False
        ok = reply is not None and reply.get("ok") is True
        self.status[i] = OK if ok else (ERROR if reply is not None
                                        else NO_REPLY)
        if self.op[i] == UPSERT and ok:
            self.pid[i] = int(reply["pid"])
            self.i += 1
            return
        if self.op[i] == DELETE and ok:
            self.key_pid[self.key[i]] = self.pid[i - 1]
        # a failed upsert leaves its key as it was: its delete is skipped
        self.i += 1 if self.op[i] == DELETE else 2

    def record(self, t0: float) -> dict:
        return {"w_due": self.due, "w_sent": self.sent - t0,
                "w_ack": self.ack - t0, "w_status": self.status,
                "w_op": self.op, "w_key": self.key, "w_pid": self.pid,
                "w_version": self.version}


def _send_blocking(sock, line: bytes, buf: bytearray) -> dict:
    sock.sendall(line)
    while not buf.endswith(b"\n"):
        chunk = sock.recv(1 << 16)
        if not chunk:
            raise ConnectionError("the server closed the writer connection")
        buf += chunk
    reply = json.loads(buf)
    buf.clear()
    return reply


def preload(writer: Writer, n: int, port: int):
    """Send the first ``n`` updates, each as soon as the one before it
    is acknowledged, during set-up."""
    sock = socket.create_connection(("127.0.0.1", port))
    sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    buf = bytearray()
    with sock:
        while writer.i < 2 * n:
            line = writer.take(time.monotonic())
            try:
                reply = _send_blocking(sock, line, buf)
            except OSError:
                reply = None
            writer.reply(time.monotonic(), reply)


class Conn:
    def __init__(self, port: int):
        self.sock = socket.create_connection(("127.0.0.1", port))
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.sock.setblocking(False)
        self.out = memoryview(b"")
        self.inbuf = bytearray()
        self.req = -1


def drive(reqs, due, port: int, t0: float, drain_until: float, k: int,
          writer: Writer | None = None):
    """Send ``reqs[i]`` at ``t0 + due[i]``, and the writer's operations
    as they fall due; → per-request send and reply times (monotonic),
    status, pids and scores."""
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
    wconn = None
    if writer is not None:
        wconn = Conn(port)
        sel.register(wconn.sock, selectors.EVENT_READ, wconn)
    i = inflight = 0
    while True:
        now = time.monotonic()
        w_due = None if writer is None else writer.next_due()
        if w_due is not None and t0 + w_due <= now:
            wconn.out = memoryview(writer.take(now))
            _send(sel, wconn)
            w_due = None
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
        w_left = writer is not None and writer.i < len(writer.due)
        if i == n and ((inflight == 0 and not w_left)
                       or now >= drain_until):
            break
        wait = (due_abs[i] if i < n else drain_until) - now
        if w_due is not None:
            wait = min(wait, t0 + w_due - now)
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
                if c is wconn:
                    if writer.busy:
                        writer.reply(time.monotonic(), None)
                    writer.i = len(writer.due)   # nothing more is sent
                    wconn = None
                    continue
                if c.req >= 0 and status[c.req] == NO_REPLY:
                    status[c.req], done[c.req] = ERROR, time.monotonic()
                    inflight -= 1
                continue
            if chunk:
                c.inbuf += chunk
                if c.inbuf.endswith(b"\n") and c is wconn:
                    writer.reply(time.monotonic(), json.loads(c.inbuf))
                    c.inbuf.clear()
                elif c.inbuf.endswith(b"\n"):
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
    ap.add_argument("--keys", default=None)
    args = ap.parse_args(argv)
    cfg = harness.load_json(args.config)
    traffic = harness.load_json(args.traffic)
    due, w_due = split(traffic, schedule(traffic, args.seconds, args.seed),
                       args.seed)
    docs = gen.make_corpus(harness.corpus(cfg), args.seed)
    reqs = encode_requests(cfg, traffic, args.seed, len(due), docs)
    writer = None
    if "writes" in traffic:
        n_pre = traffic["writes"].get("preload", 0)
        keys, versions, lines = encode_updates(cfg, traffic, args.seed,
                                               len(w_due), docs)
        key_pid = np.arange(cfg["n_docs"], dtype=np.int64)
        if args.keys and pathlib.Path(args.keys).exists():
            key_pid = np.load(args.keys)
    print(f"READY {len(reqs)}", flush=True)
    line = sys.stdin.readline().split()
    if "writes" in traffic:
        # without PRELOAD (a later window of a sweep) only the window's
        # updates are sent
        first = 0 if line[:1] == ["PRELOAD"] else n_pre
        writer = Writer(keys[first:], np.arange(first, len(keys)), lines,
                        np.r_[np.full(n_pre - first, -np.inf), w_due],
                        key_pid)
    if line[:1] == ["PRELOAD"] and writer is not None:
        preload(writer, n_pre, int(line[1]))
        terms = {"term_ids": versions["term_ids"][:n_pre].tolist(),
                 "term_weights": versions["term_weights"][:n_pre].tolist()}
        print(f"PRELOADED {json.dumps(terms)}", flush=True)
        line = sys.stdin.readline().split()
    if len(line) != 3 or line[0] != "GO":
        raise SystemExit("client: no GO from the server process")
    port, t0 = int(line[1]), float(line[2])
    sent, done, status, pids, scores = drive(
        reqs, due, port, t0, t0 + args.seconds + args.drain,
        traffic["k"], writer)
    extra = {} if writer is None else writer.record(t0)
    np.savez(args.out, due=due, sent=sent - t0, done=done - t0,
             status=status, pids=pids, scores=scores, **extra)
    if writer is not None and args.keys:
        np.save(args.keys, writer.key_pid)
    print("DONE", flush=True)


if __name__ == "__main__":
    main()
