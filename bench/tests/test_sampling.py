"""Which streams a fleet run judges: on one chip the seed's one draw over
all rows, as it has always been; on several, an equal share drawn from
each chip's shard, so that a fault confined to one shard shows."""

import numpy as np
import pytest

from bench.core.cell import load_cell

SEEDS = [1, 2315000101, 4294967301]


def _cell():
    cell = load_cell("fleet-devops-L2")
    n = int(cell.config["hosts"]) * int(cell.config["metrics_per_host"])
    return cell, n


def _rows(chips, seed):
    cell, _ = _cell()
    return cell.system.System(cell, seed, [None] * chips).rows


@pytest.mark.parametrize("seed", SEEDS)
def test_one_chip_draws_as_before(seed):
    cell, n = _cell()
    rng = np.random.default_rng(seed)
    before = np.sort(rng.choice(n, min(cell.system.SAMPLE_STREAMS, n),
                                replace=False))
    rows = _rows(1, seed)
    assert rows.dtype == before.dtype
    np.testing.assert_array_equal(rows, before)


@pytest.mark.parametrize("seed", SEEDS)
def test_every_shard_gets_its_share(seed):
    cell, n = _cell()
    rows = _rows(4, seed)
    share = cell.system.SAMPLE_STREAMS // 4
    assert np.bincount(rows // (n // 4), minlength=4).tolist() == [share] * 4
    assert np.all(np.diff(rows) > 0)
    np.testing.assert_array_equal(rows, _rows(4, seed))
