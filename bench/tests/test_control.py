"""The control comes out as not correct: the plain reference put in the
program's place with its samples held in bfloat16, the precision below
the float32 that each configuration states, fails a limit of every cell.
The reference itself, in the program's place, passes them."""

import numpy as np
import pytest

from bench.core import check
from bench.core.cell import load_cell
from bench.tests.test_rehearsal import WORKLOADS


def _samples(cell, n_streams=8, n_points=1500, seed=3):
    rng = np.random.default_rng(seed)
    walk = cell.config["walk"]
    v = rng.uniform(walk["lo"], walk["hi"], n_streams)
    ys = np.empty((n_streams, n_points), np.float32)
    for t in range(n_points):
        v = np.clip(v + walk["step_sd"] * rng.standard_normal(n_streams),
                    walk["lo"], walk["hi"])
        ys[:, t] = v
    t0, dt = cell.config.get("t0", 0.0), cell.config.get("dt", 1.0)
    ts = t0 + dt * np.arange(n_points, dtype=np.float64)
    return [check.Sample(i, ts, ys[i], None) for i in range(n_streams)]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_fails_and_reference_passes(workload):
    cell = load_cell(workload)
    tr, eps = cell.traffic, float(cell.config["eps"])
    samples = _samples(cell)
    args = (samples, tr["method"], tr["protocol"], eps, cell.system.MAX_RUN)
    ref = check.reference_answers(*args)
    ctrl = check.reference_answers(*args, bf16=True)
    assert all(r["ok"] for r in
               check.judge(check.numbers(samples, ref, ref, eps),
                           cell.limits))
    rows = check.judge(check.numbers(samples, ctrl, ref, eps), cell.limits)
    assert not all(r["ok"] for r in rows), rows
