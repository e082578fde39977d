"""A cell as ``BENCHMARK.json`` and the files beside it describe it.

Everything that belongs to one configuration, one traffic mix, one
metric or one cell is a file of its own, found by name:

- ``bench/configs/<config>.json``: the deployment (its ``system``, sizes,
  eps, the reference method and protocol, and its source);
- ``bench/traffic/<traffic>.json``: the load a cell offers;
- ``bench/systems/<system>.py``: how a run drives that kind of system;
- ``bench/metrics/<metric>.py``: the reader of one metric;
- ``bench/limits/<workload>.json``: the limit of each compared number.

``bench/pending.json`` holds, in ``BENCHMARK.json``'s form, cells that are
written but not yet proved on the chip: ``load_cell`` finds them too, so
the tools and the tests run them by name.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import List

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def _load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


_MODULES: dict = {}


def load_module(path: Path):
    """Import a file by path, once (metric files are named after metrics,
    which may hold dots)."""
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            "bench_file_" + path.stem.replace(".", "_").replace("-", "_"),
            path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _MODULES[path] = mod
    return _MODULES[path]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: List[dict]
    per_layer: List[dict]

    @property
    def system(self):
        return load_module(BENCH_DIR / "systems" / f"{self.config['system']}.py")

    def reader(self, metric: str):
        return load_module(BENCH_DIR / "metrics" / f"{metric}.py").read


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in reported
    return True


def catalog(root: Path = ROOT) -> dict:
    """``BENCHMARK.json`` with the pending cells and their entries."""
    bench = _load_json(root / "BENCHMARK.json")
    pending = root / "bench" / "pending.json"
    if pending.is_file():
        extra = _load_json(pending)
        for key in ("configs", "workloads", "end_to_end", "per_layer"):
            bench[key] = bench[key] + extra.get(key, [])
    return bench


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = catalog(root)
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                       f"have {sorted(work)}")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = _load_json(root / conf["file"])
    traffic = _load_json(BENCH_DIR / "traffic" / f"{w['traffic']}.json")
    limits = _load_json(BENCH_DIR / "limits" / f"{name}.json")
    e2e = [m for m in bench["end_to_end"] if _applies(m, name, set())]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, name, reported)]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, limits=limits, end_to_end=e2e,
                per_layer=per_layer)
