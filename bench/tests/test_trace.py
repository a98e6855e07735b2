"""The trace reducer, on hand-made intervals and on a small trace
recorded on one v5e chip (``data/v5e-hybrid-trace.textproto.gz``: 0.45 s
of a traced ``msmarco-hybrid.steady`` run, the device's ``XLA Modules``
and ``XLA Ops`` lines and the host's annotation spans, op names
shortened, stored as the text form of the XSpace proto)."""

import gzip
import pathlib

import numpy as np
import pytest
from jax.profiler import ProfileData

import trace

FIXTURE = pathlib.Path(__file__).resolve().parent / "data" / \
    "v5e-hybrid-trace.textproto.gz"


def test_interval_arithmetic_by_hand():
    iv = np.array([[5, 7], [0, 2], [1, 3], [6, 6.5], [8, 9]], float)
    u = trace.union(iv)
    assert u.tolist() == [[0, 3], [5, 7], [8, 9]]
    assert trace.complement(u, -1, 10).tolist() == [[-1, 0], [3, 5],
                                                    [7, 8], [9, 10]]
    assert trace.overlap(u, np.array([[2.5, 5.5], [8.5, 20]])) == 1.5


def test_op_names():
    assert trace.op_name("%fusion = f32[11796480]{0:T(1024)} fusion("
                         "f32[8,32,32768]{2,1,0} %a)") == \
        "%fusion = f32[11796480] fusion"
    assert trace.op_name("plain") == "plain"


@pytest.fixture(scope="module")
def raw():
    text = gzip.open(FIXTURE, "rt").read()
    pd = ProfileData.from_serialized_xspace(
        ProfileData.text_proto_to_serialized_xspace(text))
    return trace.read(pd)


def _merged_length(events):
    """Busy time by a plain sweep over the sorted intervals."""
    total, end = 0.0, -np.inf
    for _, a, b in sorted(events, key=lambda e: e[1]):
        if a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def test_recorded_trace(raw):
    dev = raw["devices"]["/device:TPU:0"]
    assert len(dev["modules"]) == 4 and len(dev["ops"]) == 213
    r = trace.reduce(raw, "fused_hybrid_tail")
    lo, hi = raw["extent"]
    assert r["window_s"] == pytest.approx(hi - lo)
    assert r["busy_s"] == pytest.approx(_merged_length(dev["ops"]),
                                        rel=1e-12)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["program_calls"] == 4
    assert r["program_device_s"] == pytest.approx(
        sum(b - a for _, a, b in dev["modules"]))
    assert trace.reduce(raw, "no_such_program")["program_calls"] == 0
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= trace.TOP
    assert ops[0][0].startswith("jit_fused_hybrid_tail: ")
    assert all(a[1] >= b[1] for a, b in zip(ops, ops[1:]))
    # idle time = the spans' share plus the share no span covers
    gaps = dict(r["breakdown"]["idle_gaps"])
    idle = r["window_s"] - r["busy_s"]
    spans = trace.union(trace._iv(raw["spans"]))
    gap_iv = trace.complement(trace.union(trace._iv(dev["ops"])), lo, hi)
    assert gaps["host:no_span"] == pytest.approx(
        idle - trace.overlap(gap_iv, spans))
    assert all(k.split(":")[0] in ("stage", "tcp", "tail", "host")
               for k in gaps)
