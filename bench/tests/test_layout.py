"""``BENCHMARK.json``, ``bench/pending.json`` and the files they name:
every cell, configuration, traffic mix, metric and limit is where the
harness looks for it, and every name and unit is made of the characters
the format allows."""

import json
import re
from pathlib import Path

import pytest

from bench.core.cell import BENCH_DIR, catalog, load_cell

ROOT = BENCH_DIR.parent
BENCH = catalog()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_names_and_units():
    names = [c["name"] for c in BENCH["configs"]] + \
        [w["name"] for w in BENCH["workloads"]] + \
        [w["traffic"] for w in BENCH["workloads"]] + \
        [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert all(NAME.match(n) for n in names), names
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_cell_files(workload):
    cell = load_cell(workload)
    assert (BENCH_DIR / "systems" / f"{cell.config['system']}.py").is_file()
    assert {"shape_mismatch_pct", "error_eps"} <= set(cell.limits) <= {
        "shape_mismatch_pct", "value_gap_eps", "error_eps"}
    for m in cell.end_to_end + cell.per_layer:
        assert callable(cell.reader(m["name"]))
    assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for mod in (cell.traffic["method"], cell.traffic["protocol"]):
        assert (BENCH_DIR / "reference" / f"{mod}.py").is_file()


def test_configs_are_files_of_their_own():
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    for c in BENCH["configs"]:
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"]
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]


def test_benchmark_stands_without_the_pending_cells():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"] for w in bench["workloads"]}
    assert {w["config"] for w in bench["workloads"]} == \
        {c["name"] for c in bench["configs"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m["name"]
    assert cells.isdisjoint(
        w["name"] for w in BENCH["workloads"][len(bench["workloads"]):])


def test_chip_counts():
    """Every cell asks for 1 or 4 chips, and at most half the benchmark's
    cells (rounded down, but always one) ask for four."""
    assert {w["chips"] for w in BENCH["workloads"]} <= {1, 4}
    cells = json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]
    four = [w["name"] for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 2), four


def test_metric_workloads_name_cells():
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m.get("workloads", ())) <= cells, m["name"]
