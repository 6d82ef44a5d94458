"""The hybrid Mamba-2 / latent-expert family's tiny cell through the
harness on the CPU: ``correct`` through ``Optimizer`` -> ``LocalOptimizer``,
and not ``correct`` under the lower-precision controls and under each of the
configuration's faults (``bm_faults``). The layers themselves are in
``test_bm_nemotron3.py``."""
import pytest

import bm_faults
import bm_util
from benchmark import harness

CELL = "tiny_nemotron3"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bm_util.tiny_root(tmp_path_factory.mktemp("nemotron3_cell"))


@pytest.fixture(scope="module")
def cell(root):
    return harness.load_cell(CELL, root)


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
    ("fault_chunk_reset", "grad_diff"), ("fault_no_conv", "grad_diff"),
    ("fault_ungrouped_norm", "grad_diff")])
def test_the_control_and_every_fault_are_not_correct(cell, probed, case,
                                                     fails):
    """The reference in the program's place in bfloat16, and with half of
    every row's targets, the held experts' output, the state's passage
    between chunks, the causal convolution or the norm's groups left out:
    each fails the limit named, by the run's own comparison."""
    line = probed[case]
    assert line["correct"] is False, line
    assert line[fails] > cell["limits"][fails], line


def test_the_faults_of_the_configuration_are_the_issues_five(cell):
    assert set(bm_faults.cases_of(cell)) == {
        "control_bf16", "control_bf16_pass", "fault_half_batch",
        "fault_no_routed", "fault_chunk_reset", "fault_no_conv",
        "fault_ungrouped_norm"}
    with pytest.raises(ValueError, match="no fault"):
        cell["parts"].reference.train_steps(
            None, [], cell["config_data"]["model"],
            cell["config_data"]["entry"]["optimizer"], fault="no_such")
