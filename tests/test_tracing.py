"""The server's own profiler spans and counters: every request and every
plan stage of its micro-batch show up in a profile of the serving
process, joined by request id; gather stages count the bytes they hand
to the device; compiles are counted; and tracing leaves answers alone."""

import json
import pathlib
import socket
import threading
import time

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.multistage import MultiStageParams, MultiStageRetriever
from repro.core.plaid import PLAIDSearcher, PlaidParams
from repro.index.builder import ColBERTIndex
from repro.index.splade_index import build_splade_index
from repro.serving.engine import ServeEngine
from repro.serving.pipeline import PipelineStats
from repro.serving.server import RetrievalServer

SPAN_PREFIXES = ("tcp:", "stage:", "tail:")


@pytest.fixture(scope="module")
def retr(built_index, small_corpus):
    index = ColBERTIndex(built_index, mode="mmap")
    searcher = PLAIDSearcher(index, PlaidParams(nprobe=8, candidate_cap=512,
                                                ndocs=128, k=50))
    sidx = build_splade_index(small_corpus["doc_term_ids"],
                              small_corpus["doc_term_weights"],
                              small_corpus["cfg"].vocab,
                              small_corpus["cfg"].n_docs)
    return MultiStageRetriever(sidx, searcher,
                               MultiStageParams(first_k=50, k=20))


def _payload(small_corpus, qid, method):
    return {"qid": qid, "method": method, "k": 10,
            "q_emb": small_corpus["q_embs"][qid].tolist(),
            "term_ids": small_corpus["q_term_ids"][qid].tolist(),
            "term_weights": small_corpus["q_term_weights"][qid].tolist()}


def _query(port, payload) -> dict:
    """One request on its own connection, read until the server closes
    it: a client has its reply before the server's handler leaves the
    request's spans, and closes the connection only after."""
    with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
        s.sendall((json.dumps(payload) + "\n").encode())
        s.shutdown(socket.SHUT_WR)
        buf = b""
        while chunk := s.recv(65536):
            buf += chunk
    return json.loads(buf)


def _warm(retr, small_corpus, method, max_batch):
    """Compile every micro-batch size the server can form."""
    for b in range(1, max_batch + 1):
        retr.search_batch(method, q_embs=small_corpus["q_embs"][:b],
                          term_ids=small_corpus["q_term_ids"][:b],
                          term_weights=small_corpus["q_term_weights"][:b],
                          k=10)


class _Served:
    """A pipelined server (depth 2, batches of up to 4) on an ephemeral
    TCP port. With ``start=False`` its worker starts only once the
    first :meth:`ask` has queued every request, so they form full
    batches."""

    def __init__(self, retr, start: bool = True):
        self.engine = ServeEngine(retr, pipeline_depth=2)
        self.server = RetrievalServer(self.engine, max_batch=4,
                                      batch_timeout_ms=50.0)
        if start:
            self.server.start()
        self.tcp = self.server.serve_tcp("127.0.0.1", 0)
        self.loop = threading.Thread(target=self.tcp.serve_forever,
                                     daemon=True)
        self.loop.start()

    def ask(self, payloads, concurrent: bool) -> dict:
        """Send each payload on its own connection → {qid: reply}."""
        port = self.server.tcp_port
        out = {}

        def one(p):
            out[p["qid"]] = _query(port, p)
        if not concurrent:
            for p in payloads:
                one(p)
            return out
        threads = [threading.Thread(target=one, args=(p,))
                   for p in payloads]
        for t in threads:
            t.start()
        if not self.server.running:
            deadline = time.monotonic() + 60
            while self.server.queue.qsize() < len(payloads):
                assert time.monotonic() < deadline, "requests not queued"
                time.sleep(0.001)
            self.server.start()
        for t in threads:
            t.join(timeout=60)
            assert not t.is_alive()
        return out

    def close(self):
        self.tcp.shutdown()
        self.server.shutdown_gracefully()
        self.tcp.server_close()
        self.loop.join(timeout=10)
        assert not self.loop.is_alive()
        self.engine.close()


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler → (its result, host span events:
    (thread line, name, start_ns, end_ns, stats dict))."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        result = fn()
    finally:
        jax.profiler.stop_trace()
    found = sorted(pathlib.Path(tmp_path).glob("**/*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    pd = ProfileData.from_file(str(found[-1]))
    events = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            events += [(i, e.name, e.start_ns, e.end_ns,
                        {k: v for k, v in e.stats})
                       for e in line.events
                       if e.name.startswith(SPAN_PREFIXES)]
    return result, events


def _qids(stats) -> list:
    return [int(q) for q in str(stats["qids"]).split()]


@pytest.mark.parametrize("method", ["hybrid", "colbert"])
def test_spans_name_each_request_and_its_batch_stages(retr, small_corpus,
                                                      tmp_path, method):
    _warm(retr, small_corpus, method, 4)
    served = _Served(retr, start=False)
    qids = list(range(8))
    try:
        replies, events = _traced(tmp_path, lambda: served.ask(
            [_payload(small_corpus, q, method) for q in qids],
            concurrent=True))
    finally:
        served.close()
    assert all("error" not in r for r in replies.values()), replies
    assert not [e for e in events if "#" in e[1]], "metadata in a name"

    stages = [e for e in events if e[1].startswith("stage:")
              and "qids" in e[4]]
    for name in retr.compile_plan(method).stage_names():
        assert any(e[1] == f"stage:{name}" for e in stages), name
    assert any(e[1] == "stage:assemble" for e in stages)

    requests = {}
    for e in events:
        if e[1] == "tcp:request":
            assert e[4]["qid"] not in requests, "two spans for one qid"
            requests[e[4]["qid"]] = e
    assert sorted(requests) == qids
    for qid, (line, _, a, b, _) in requests.items():
        awaits = [e for e in events if e[1] == "tcp:await"
                  and e[0] == line and a <= e[2] and e[3] <= b]
        assert len(awaits) == 1 and awaits[0][4]["qid"] == qid

    batched = 0
    for _, name, a, b, stats in stages:
        ids = _qids(stats)
        batched += len(ids) > 1
        for qid in ids:
            _, _, ra, rb, _ = requests[qid]
            assert ra <= a and b <= rb, (name, qid)
    assert batched, "no stage served more than one request"
    for name in ("tcp:collect", "tcp:dispatch", "tcp:resolve"):
        spans = [e for e in events if e[1] == name]
        assert spans and all("qids" in e[4] for e in spans), name
    assert all(e[4]["n"] == len(_qids(e[4])) for e in events
               if e[1] == "tcp:collect")


def test_compiles_are_counted_and_none_in_a_window_after_warm_up(
        retr, small_corpus):
    stats = PipelineStats()
    shape = (7, 13)                     # a shape nothing else compiles
    jax.jit(lambda x: x * 3 + 1)(np.ones(shape, np.float32))
    counters = stats.snapshot()["counters"]
    assert counters["jax_compiles"] >= 1
    assert counters["jax_compile_ms"] > 0

    _warm(retr, small_corpus, "hybrid", 4)
    served = _Served(retr)
    try:
        payloads = [_payload(small_corpus, q, "hybrid") for q in range(8)]
        served.ask(payloads, concurrent=True)          # warm the front
        before = served.server.health()["counters"]["jax_compiles"]
        served.ask(payloads, concurrent=True)
        after = served.server.health()
    finally:
        served.close()
    assert after["counters"]["jax_compiles"] == before
    gather = after["stages"]["host_gather:residuals"]
    assert gather["h2d_bytes"] > 0
    assert after["stages"]["splade_stage1"]["h2d_bytes"] == 0


@pytest.mark.parametrize("method, stage, keys", [
    ("hybrid", "host_gather:residuals", ("g_codes", "g_packed", "g_valid")),
    ("colbert", "host_gather:codes", ("codes", "cvalid")),
    ("colbert", "host_gather:residuals", ("f_codes", "f_packed",
                                          "f_valid"))])
def test_gather_stage_counts_the_bytes_it_returns(retr, small_corpus,
                                                  method, stage, keys):
    plan = retr.compile_plan(method)
    stats = PipelineStats()
    cb = retr.build_batch(method, q_embs=small_corpus["q_embs"][:3],
                          term_ids=small_corpus["q_term_ids"][:3],
                          term_weights=small_corpus["q_term_weights"][:3],
                          alphas=np.full(3, 0.3, np.float32), k=10,
                          qids=[0, 1, 2])
    for st in plan.stages:
        cb = plan.run_stage(st, cb, stats)
        if st.name == stage:
            returned = sum(np.asarray(cb.state[k]).nbytes for k in keys)
            break
    # three queries pad to four rows: the padded row's bytes count too
    assert returned > 0
    assert stats.snapshot()["stages"][stage]["h2d_bytes"] == returned


def test_answers_equal_with_and_without_a_trace(retr, small_corpus,
                                                tmp_path):
    _warm(retr, small_corpus, "hybrid", 4)
    served = _Served(retr)
    payloads = [_payload(small_corpus, q, "hybrid") for q in range(6)]
    try:
        plain = served.ask(payloads, concurrent=False)
        traced, _ = _traced(tmp_path,
                            lambda: served.ask(payloads, concurrent=False))
    finally:
        served.close()
    for qid, reply in plain.items():
        assert "error" not in reply
        assert traced[qid]["pids"] == reply["pids"]
        assert traced[qid]["scores"] == reply["scores"]
