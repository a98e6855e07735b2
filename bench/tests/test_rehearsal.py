"""CPU rehearsal of a whole run at a tiny corpus: the index build child,
the load generator child, the TCP front, the window, the end-to-end and
per-layer arithmetic, and the float64 reference."""

import json

import numpy as np
import pytest

import run
import tiny


@pytest.mark.parametrize("kind", ["hybrid", "plaid"])
def test_run_is_correct_and_reports_its_metrics(kind):
    c, bench = tiny.cell(kind)
    r = run.run(c["workload"]["name"], tiny.SEED, tiny.SECONDS, False,
                require_tpu=False, c=c, bench=bench)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 40 and r["failed"] == 0
    judged = {"hybrid": "p50_ms", "plaid": "qps"}[kind]
    assert set(r["metrics"]) == {"setup_s", judged, "host_ram_mb"}
    assert r["metrics"][judged]["value"] > 0
    assert r["device"]["platform"] == "cpu"
    assert list(r)[-1] == "checks"
    assert set(r["checks"]) == {"failed", "bad_pids", "score_err",
                                "rank_gap"}
    json.dumps(r)


HOST_LAYERS = {
    "hybrid": ("p95_ms.steady", "client_late_ms", "queue_wait_ms",
               "gather_ms", "stage1_ms", "jax_compiles.steady"),
    "plaid": ("p50_ms.plaid", "p95_ms.plaid", "client_late_ms.plaid",
              "queue_wait_ms.plaid", "gather_ms.plaid",
              "jax_compiles.plaid")}
DEVICE_LAYERS = {
    "hybrid": ("tail_device_ms", "tail_roofline", "device_idle.steady"),
    "plaid": ("tail_device_ms.plaid", "tail_roofline.plaid",
              "device_idle.plaid")}


@pytest.mark.parametrize("kind", ["hybrid", "plaid"])
def test_traced_run_reads_the_host_layers(kind):
    c, bench = tiny.cell(kind)
    r = run.run(c["workload"]["name"], tiny.SEED + 1, tiny.SECONDS, True,
                require_tpu=False, c=c, bench=bench)
    assert r["correct"], r["checks"]
    m = r["metrics"]
    assert set(m) == set(HOST_LAYERS[kind])
    for name in HOST_LAYERS[kind]:
        assert np.isfinite(m[name]["value"]), name
    # warm-up compiled every shape the window dispatches
    assert m[HOST_LAYERS[kind][-1]]["value"] == 0
    # no TPU plane in a CPU trace: the device readers find nothing
    for name in DEVICE_LAYERS[kind]:
        assert name not in m
    assert r["device"]["window_s"] > 0


def test_no_tpu_exits_nonzero_without_a_result(capsys):
    assert run.main(["--workload", "msmarco-hybrid.steady", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out = capsys.readouterr()
    assert out.out.strip() == ""
    assert "needs a TPU" in out.err
