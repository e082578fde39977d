"""Whole runs of every cell on the CPU at a tiny size, in interpret mode,
each on as many CPU devices as the cell has chips: the last line parses,
``correct`` is computed, and no device metric is reported from the CPU.
The fleet cell runs on four devices too, as a four-chip cell would.  The
entry itself refuses to run without a TPU, and without the system under
test beside it."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench.core.cell import catalog, load_cell

ROOT = Path(__file__).resolve().parents[2]
# Every cell the harness can run: those of BENCHMARK.json, then the
# pending ones.
WORKLOADS = [w["name"] for w in catalog()["workloads"]]
# An existing cell run at another chip count (``cpu_run --chips``).
FOUR_CHIPS = pytest.param("fleet-devops-L2", 4, id="fleet-devops-L2-4chips")


def _env(tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{ROOT}:{ROOT / 'src'}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return env


def cpu_run(tmp_path, workload, *extra, seed="4294967301", seconds="0.4",
            chips=None):
    if chips:
        extra += ("--chips", str(chips))
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.cpu_run", workload, seed,
         seconds, *extra], cwd=ROOT, env=_env(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize(
    "workload,chips", [pytest.param(w, None, id=w) for w in WORKLOADS]
    + [FOUR_CHIPS])
def test_cell_rehearses_on_cpu(tmp_path, workload, chips):
    result, err = cpu_run(tmp_path, workload, chips=chips)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, err[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}          # no device metric from a CPU
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == (chips or load_cell(workload).chips)
    assert set(result["checks"]) == set(load_cell(workload).limits)
    assert "compiles or cache loads inside the window: 0" in err
    assert err.rstrip().splitlines()[-1].startswith("check error_eps")


def _entry(cwd, tmp_path):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", WORKLOADS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd,
        env=_env(tmp_path), capture_output=True, text=True, timeout=300)


def test_entry_refuses_to_run_without_a_tpu(tmp_path):
    proc = _entry(ROOT, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_entry_refuses_to_run_without_the_system(tmp_path):
    alone = tmp_path / "checkout"
    alone.mkdir()
    shutil.copy(ROOT / "BENCHMARK.json", alone)
    shutil.copytree(ROOT / "bench", alone / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _entry(alone, tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
