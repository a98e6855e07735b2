"""With the timed path broken underneath, a run comes out not correct.

Each fault is planted in the program's stage-4 tail, where the answers
are produced, for one run of a tiny cell on the CPU: an answer altered
(the first two ranks of every answer swapped), and half of each
micro-batch left out (its queries given the answers of the other half;
the traffic arrives in bursts of 4 so that batches hold several)."""

import jax.numpy as jnp
import pytest

import repro.core.plaid as plaid
import run
import tiny

TAILS = {"hybrid": "fused_hybrid_tail", "plaid": "fused_rerank_topk_batch"}


def swap_first_two(scores, idx):
    return scores, idx.at[:, 0].set(idx[:, 1]).at[:, 1].set(idx[:, 0])


def half_batch(scores, idx):
    h = -(-idx.shape[0] // 2)
    rows = jnp.arange(idx.shape[0]) % h
    return scores[rows], idx[rows]


@pytest.mark.parametrize("fault", [swap_first_two, half_batch])
@pytest.mark.parametrize("kind", ["hybrid", "plaid"])
def test_fault_is_not_correct(kind, fault, monkeypatch):
    name = TAILS[kind]
    tail = getattr(plaid, name)
    monkeypatch.setattr(plaid, name,
                        lambda *a, **kw: fault(*tail(*a, **kw)))
    c, bench = tiny.cell(kind, "tiny-burst")
    r = run.run(c["workload"]["name"], tiny.SEED, tiny.SECONDS, False,
                require_tpu=False, c=c, bench=bench)
    assert r["correct"] is False, r["checks"]
