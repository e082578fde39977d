"""Whole runs of every cell on the CPU at a tiny size, in interpret mode:
the last line parses, ``correct`` is computed, and no device metric is
reported from the CPU.  The entry itself refuses to run without a TPU,
and without the system under test beside it."""

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


def _env(tmp_path):
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=f"{ROOT}:{ROOT / 'src'}",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"))
    return env


def cpu_run(tmp_path, workload, *extra, seed="4294967301", seconds="0.4"):
    proc = subprocess.run(
        [sys.executable, "-m", "bench.tests.cpu_run", workload, seed,
         seconds, *extra], cwd=ROOT, env=_env(tmp_path),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_rehearses_on_cpu(tmp_path, workload):
    result, err = cpu_run(tmp_path, workload)
    assert list(result)[:5] == ["correct", "attempted", "failed", "metrics",
                                "device"]
    assert list(result)[-1] == "checks"
    assert result["correct"] is True, err[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["metrics"] == {}          # no device metric from a CPU
    assert result["device"]["platform"] == "cpu"
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
