"""The timed path broken underneath makes ``correct`` come out false, for
each fault a cell can have: a step that hands back its state unchanged,
half of the streams' answers left out, an answer altered where it is
produced, and, in a cell on more than one chip, a shard's rows given
another shard's bytes, or one shard's answers altered.  (The shards of a fleet exchange nothing, so
there is no exchange between chips to leave out.)"""

import json
import pytest

from bench.core.cell import load_cell
from bench.tests.test_rehearsal import FOUR_CHIPS, WORKLOADS, cpu_run

FAULTS = ["state_unchanged", "half_dropped", "answer_altered"]
# Every cell on more than one chip at its own count, and an existing
# cell run on four.
MULTI_CHIP = [pytest.param(w, None, id=w) for w in WORKLOADS
              if load_cell(w).chips > 1] + [FOUR_CHIPS]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_not_correct(tmp_path, workload, fault):
    result, err = cpu_run(tmp_path, workload, fault, seconds="0.3")
    assert result["correct"] is False, json.dumps(result["checks"])


@pytest.mark.parametrize("workload,chips", MULTI_CHIP)
def test_misplaced_shard_is_not_correct(tmp_path, workload, chips):
    result, err = cpu_run(tmp_path, workload, "shard_misplaced",
                          seconds="0.3", chips=chips)
    assert result["device"]["count"] > 1
    assert result["correct"] is False, json.dumps(result["checks"])


@pytest.mark.parametrize("workload,chips", MULTI_CHIP)
def test_one_shard_altered_is_not_correct(tmp_path, workload, chips):
    result, err = cpu_run(tmp_path, workload, "shard_altered",
                          seconds="0.3", chips=chips)
    assert result["device"]["count"] > 1
    assert result["correct"] is False, json.dumps(result["checks"])
