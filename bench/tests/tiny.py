"""A CPU-sized cell for the tests: the benchmark's own configuration
keys at small sizes (``data/tiny-*.json``), its metric lists extended to
the tiny cells."""

import pathlib

import harness

DATA = pathlib.Path(__file__).resolve().parent / "data"
SEED = 2**31 + 11              # larger than 32 signed bits hold
SECONDS = 2.0


def cell(kind: str, traffic: str = "tiny-traffic"):
    """→ (cell, bench) for ``run.run`` with ``kind`` hybrid, plaid or
    live (hybrid serving a live index)."""
    name = f"tiny-{kind}"
    like = {"hybrid": "msmarco-hybrid.steady",
            "plaid": "msmarco-plaid.steady",
            "live": "msmarco-hybrid.steady"}[kind]
    bench = harness.benchmark()
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            if like in m.get("workloads", []):
                m["workloads"] = m["workloads"] + [name]
    cfg_file, tr_file = DATA / f"{name}.json", DATA / f"{traffic}.json"
    c = {"workload": {"name": name, "config": name, "traffic": traffic,
                      "chips": 1},
         "config_file": cfg_file, "traffic_file": tr_file,
         "config": harness.load_json(cfg_file),
         "traffic": harness.load_json(tr_file)}
    return c, bench
