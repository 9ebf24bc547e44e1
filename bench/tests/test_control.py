"""The comparison that decides ``correct`` fails its control and each
fault the timed path of a serving cell can have, at rehearsal size."""
import pytest

from _drive import FAULTS, cells, control, control_passed, faulted

import harness


@pytest.mark.parametrize("cell", cells())
def test_fp8_control_fails_a_limit_the_system_passes(cell):
    checks = control(cell, 11)
    assert harness.passed(checks)
    assert not control_passed(checks)


@pytest.mark.parametrize("fault", list(FAULTS))
@pytest.mark.parametrize("cell", cells())
def test_a_broken_timed_path_is_not_correct(cell, fault):
    r = faulted(cell, fault, 5)
    assert r["correct"] is False
    # the comparison caught it: every LLM had served tokens to compare
    assert all(c["requests"] > 0 for c in r["checks"].values())
