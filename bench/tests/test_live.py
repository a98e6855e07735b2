"""The harness under writes, on the CPU at a tiny size: a live index
served under a read/write mix (``tiny-ycsb``: Zipfian queries, a fifth
of the arrivals updates, two preloaded, a compaction every three
upserts) comes out correct, and its control, the same answers judged
with the writes forgotten, does not; the reference's write states in
the three timing cases of a query and a write."""

import numpy as np
import pytest

import build
import gen
import harness
import reference
import run
import tiny


def _run(forget_writes: bool):
    c, bench = tiny.cell("live", "tiny-ycsb")
    return run.run(c["workload"]["name"], tiny.SEED, tiny.SECONDS, False,
                   require_tpu=False, c=c, bench=bench,
                   forget_writes=forget_writes)


def test_live_run_is_correct_and_every_write_acknowledged():
    r = _run(False)
    assert r["correct"], r["checks"]
    w = r["info"]["writes"]
    assert r["failed"] == 0 and w["acked"] == w["sent"] > 0
    assert w["compactions_in_window"] >= 1
    assert set(r["metrics"]) == {"setup_s", "p50_ms", "host_ram_mb"}
    assert list(r)[-1] == "checks"


def test_writes_forgotten_is_not_correct():
    r = _run(True)
    checks = r["checks"]
    assert r["correct"] is False and r["failed"] == 0
    assert (checks["bad_pids"]["value"] > checks["bad_pids"]["limit"]
            or checks["rank_gap"]["value"] > checks["rank_gap"]["limit"])


@pytest.fixture(scope="module")
def one_update(tmp_path_factory):
    """A tiny index, one update (an upsert, then the delete of the key's
    passage) and a query whose answer holds that passage: the reference's
    own answers before the update (``before``) and after (``after``)."""
    c, _ = tiny.cell("live", "tiny-ycsb")
    cfg, traffic = c["config"], c["traffic"]
    out = tmp_path_factory.mktemp("index")
    build.main(["--config", str(c["config_file"]), "--seed",
                str(tiny.SEED), "--out", str(out)])
    corpus = harness.corpus(cfg)
    n_base, k, n = corpus["n_docs"], traffic["k"], 64
    key = int(gen.write_keys(corpus, traffic["writes"], 1, tiny.SEED)[0])
    log = {"w_op": np.array([0, 1], np.int8),
           "w_pid": np.array([n_base, key]), "w_key": np.array([key, key]),
           "w_version": np.array([0, -1]),
           "w_status": np.zeros(2, np.int8)}
    docs = gen.make_corpus(corpus, tiny.SEED)
    q = gen.make_queries(corpus, docs, n, tiny.SEED, gen.query_rel(
        corpus, traffic, n, tiny.SEED))
    extra = reference.upserted(cfg, traffic, log, docs, tiny.SEED, n_base)
    index = reference.Index(out, corpus["dim"], cfg["index"]["nbits"],
                            docs["doc_lens"])
    index.versions = reference.Versions(index, extra["embs"],
                                        extra["lens"])
    splade = reference.Splade(docs, extra)
    alive = np.ones(n_base + 1, bool)
    alive[n_base] = False
    after = alive.copy()
    after[[n_base, key]] = True, False
    p = dict(cfg["serving"], k=k)

    def answer(i, state):
        cand, ref, _ = reference.hybrid_outcomes(
            index, splade, q["q_embs"][i], q["q_term_ids"][i],
            q["q_term_weights"][i], p, [state])[0]
        top = sorted(ref, key=lambda x: (-ref[x], x))[:k]
        return np.array(top), np.array([ref[x] for x in top])
    for i in range(n):
        before = answer(i, alive)
        if key in before[0].tolist():
            break
    else:
        pytest.fail("no query's answer holds the updated passage")
    return {"cfg": cfg, "traffic": traffic, "index": out, "log": log,
            "i": i, "n": n, "answers": {"before": before,
                                        "after": answer(i, after)}}


# (write sends, write acknowledgements, query send, query reply) in s
TIMINGS = {
    "acknowledged_before_the_send": ((0.1, 0.2), (0.15, 0.25), 0.3, 0.4),
    "sent_after_the_reply": ((0.1, 0.2), (0.15, 0.25), 0.0, 0.05),
    "concurrent": ((0.1, 0.2), (0.15, 0.25), 0.12, 0.3),
}
ADMITS = {"acknowledged_before_the_send": {"after"},
          "sent_after_the_reply": {"before"},
          "concurrent": {"before", "after"}}


@pytest.mark.parametrize("timing", sorted(TIMINGS))
def test_write_states_by_timing(one_update, timing):
    u = one_update
    w_sent, w_ack, sent, done = TIMINGS[timing]
    verdict = {}
    for name, (pids, scores) in u["answers"].items():
        k = u["traffic"]["k"]
        client = dict(u["log"], w_sent=np.array(w_sent),
                      w_ack=np.array(w_ack),
                      status=np.zeros(u["n"], np.int8),
                      sent=np.full(u["n"], sent), done=np.full(u["n"], done),
                      pids=np.full((u["n"], k), -1, np.int64),
                      scores=np.full((u["n"], k), np.nan))
        client["pids"][u["i"], :len(pids)] = pids
        client["scores"][u["i"], :len(pids)] = scores
        numbers = reference.check(u["cfg"], u["traffic"], u["index"],
                                  tiny.SEED, client, np.array([u["i"]]))
        limits = u["cfg"]["check"]["limits"]
        verdict[name] = all(numbers[m] <= v for m, v in limits.items())
    assert {n for n, ok in verdict.items() if ok} == ADMITS[timing]
