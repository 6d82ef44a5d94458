"""The gated-latent-attention expert family's tiny cell through the harness on
the CPU: ``correct`` through ``Optimizer`` -> ``LocalOptimizer`` with
``ParallelCriterion``, and not ``correct`` under the lower-precision control
and under each of the configuration's faults (``bm_faults``). The layers
themselves are in ``test_bm_instella.py``."""
import pytest

import bm_faults
import bm_util
from benchmark import harness

CELL = "tiny_instella"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bm_util.tiny_root(tmp_path_factory.mktemp("instella_cell"))


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


# ---- the tiny cell through the harness

def test_the_cell_runs_through_the_trainer_and_is_correct(root):
    out = bm_util.run_tiny(root, CELL)
    assert out["correct"] is True, out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["metrics"] == {} and "rehearsal" in out
    assert set(out["compared"]) == set(
        harness.load_cell(CELL, root)["limits"])


@pytest.fixture(scope="module")
def probed(cell):
    return {x["what"]: x for x in bm_faults.probe(cell, [7])}


@pytest.mark.parametrize("case,fails", [
    ("control_bf16", "delta_norm_gap"), ("control_bf16_pass", "grad_diff"),
    ("fault_half_batch", "grad_diff"), ("fault_no_routed", "grad_norm_gap"),
    ("fault_no_shared", "grad_norm_gap"), ("fault_no_mtp", "grad_norm_gap"),
    ("fault_no_bias", "grad_diff")])
def test_the_control_and_every_fault_are_not_correct(cell, probed, case,
                                                     fails):
    """The reference in the program's place in bfloat16, and with half the
    batch, the held experts' output, the shared experts, the MTP loss or
    the calibrated bias left out: each fails the limit named, by the run's
    own comparison."""
    line = probed[case]
    assert line["correct"] is False, line
    assert line[fails] > cell["limits"][fails], line


def test_the_faults_of_the_configuration_are_the_issues_five(cell):
    assert set(bm_faults.cases_of(cell)) == {
        "control_bf16", "control_bf16_pass", "fault_half_batch",
        "fault_no_routed", "fault_no_shared", "fault_no_mtp", "fault_no_bias"}
    with pytest.raises(ValueError, match="no fault"):
        cell["parts"].reference.train_steps(
            None, [], cell["config_data"]["model"],
            cell["config_data"]["entry"]["optimizer"], fault="no_such")
