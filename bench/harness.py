"""Paths, files and the JAX set-up shared by the benchmark's processes.

Importing this module imports neither JAX nor the program: the load
generator imports it too, and must stay off the chip.
"""

from __future__ import annotations

import json
import os
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE = HERE / ".cache" / "jax"
WORK = HERE / ".work"

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def use_compile_cache():
    """Keep JAX's persistent compilation cache at the fixed directory
    ``bench/.cache/jax`` of this checkout, for every program however
    short its compile, so that only a checkout's first run compiles.
    Call before JAX is imported; children inherit the setting."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    os.environ["JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES"] = "0"


def device_info(devices) -> dict:
    d0 = devices[0]
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}


def load_json(path) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def benchmark() -> dict:
    return load_json(ROOT / "BENCHMARK.json")


def cell(name: str) -> dict:
    """A cell found by its name in ``BENCHMARK.json``: its workload
    entry, its configuration and traffic files, and their contents."""
    bench = benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: "
                         f"{sorted(cells)}")
    w = cells[name]
    cfg_file = ROOT / {c["name"]: c for c in bench["configs"]}[
        w["config"]]["file"]
    traffic_file = HERE / "traffic" / f"{w['traffic']}.json"
    return {"workload": w, "config_file": cfg_file,
            "traffic_file": traffic_file, "config": load_json(cfg_file),
            "traffic": load_json(traffic_file)}


def corpus(cfg: dict) -> dict:
    """The generator's parameters of a configuration: its ``corpus``
    group with the passage count."""
    return dict(cfg["corpus"], n_docs=cfg["n_docs"])


def survivors(serving: dict) -> int:
    """Passages PLAID decompresses and scores exactly: a quarter of its
    ``ndocs``, the share its full centroid interaction keeps. The
    program's ``ndocs`` counts these."""
    return serving["ndocs"] // 4


def n_requests(traffic: dict, seconds: float) -> int:
    """Requests due in a window: the cell's rate times its length, in
    whole bursts."""
    bursts = max(1, round(traffic["rate_per_s"] * seconds
                          / traffic["burst"]))
    return bursts * traffic["burst"]
