"""A run with the timed path broken underneath comes out not correct:
once for each fault the cells can have (their one chip exchanges
nothing, so there is no exchange between chips to leave out)."""
import pytest

from bench_cells import run
from bench import faults


def _learned():
    return run("ours.suite-125", seconds=1.0, paper_predictor=False, scale=0.1, workloads=2)


def test_clean_small_run_is_correct():
    assert _learned()["correct"]


@pytest.mark.parametrize("fault, number", [
    ("unchanged_state", "train_gap_ratio"),
    ("half_batch", "train_gap_ratio"),
    ("half_batch_some", "train_gap_ratio"),
    ("altered_prediction", "pred_gap"),
    ("altered_table", "table_mismatch"),
    ("altered_segment", "sim_mismatch"),
])
def test_learned_fault_is_caught(fault, number):
    with faults.LEARNED[fault]():
        out = _learned()
    assert not out["correct"]
    assert out["checks"][number]["value"] > out["checks"][number]["limit"], out["checks"]


def test_sweep_counter_altered_where_it_is_made():
    with faults.altered_sweep():
        out = run("sweep.suite", seconds=1.0, scale=0.05)
    assert not out["correct"] and out["checks"]["sim_mismatch"]["value"] > 0
