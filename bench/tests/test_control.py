"""The control at a size a test run can hold: the reference's own
pipeline in the program's place, its dots at ``high`` (three bf16
passes) where the configuration states ``highest``, comes out as not
correct under the configuration's limits; at ``highest`` it passes."""

import numpy as np
import pytest

import build
import control
import harness
import reference
import stats
import tiny


@pytest.mark.parametrize("kind", ["hybrid", "plaid"])
def test_one_precision_step_down_fails(kind, tmp_path):
    c, _ = tiny.cell(kind)
    cfg, k = c["config"], c["traffic"]["k"]
    n = harness.n_requests(c["traffic"], tiny.SECONDS)
    build.main(["--config", str(c["config_file"]), "--seed",
                str(tiny.SEED), "--out", str(tmp_path)])
    client = {"status": np.zeros(n, np.int8)}
    sample = stats.sample(client, cfg["check"]["sample"], tiny.SEED)
    limits = cfg["check"]["limits"]
    verdict = {}
    for precision in ("highest", "high"):
        client["pids"], client["scores"] = control.answers(
            cfg, tmp_path, tiny.SEED, n, sample, k, precision)
        numbers = reference.check(cfg, c["traffic"], tmp_path, tiny.SEED,
                                  client, sample)
        verdict[precision] = all(numbers[m] <= v for m, v in limits.items())
    assert verdict == {"highest": True, "high": False}
