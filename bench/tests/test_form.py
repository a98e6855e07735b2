"""``BENCHMARK.json`` against the readers and the cells it names."""

import harness
import layers

BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]


def _reports(group, cell):
    return {m["name"] for m in BENCH[group]
            if cell in m.get("workloads", [cell])}


def test_every_per_layer_metric_has_a_reader():
    for m in BENCH["per_layer"]:
        assert callable(layers.reader(m["name"])), m["name"]


def test_every_cell_reports_set_up_another_metric_and_a_layer():
    for cell in CELLS:
        e2e = _reports("end_to_end", cell)
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert _reports("per_layer", cell), cell


def test_a_layer_moves_a_metric_each_of_its_cells_reports():
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            assert m["moves"] in _reports("end_to_end", cell), (
                m["name"], cell)
