"""The timed path broken underneath makes ``correct`` come out false, for
each fault a cell can have: a step that hands back its state unchanged,
half of the streams' answers left out, an answer altered where it is
produced.  (There is one chip per cell, so no exchange between chips.)"""

import json
import pytest

from bench.tests.test_rehearsal import WORKLOADS, cpu_run

FAULTS = ["state_unchanged", "half_dropped", "answer_altered"]


@pytest.mark.parametrize("fault", FAULTS)
@pytest.mark.parametrize("workload", WORKLOADS)
def test_fault_is_not_correct(tmp_path, workload, fault):
    result, err = cpu_run(tmp_path, workload, fault, seconds="0.3")
    assert result["correct"] is False, json.dumps(result["checks"])
