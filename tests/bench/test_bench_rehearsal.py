"""The benchmark on the CPU: it refuses to measure there, it refuses to
run outside the repository, and a small run of each cell with the
control in the program's place comes out not correct."""
import os
import shutil
import subprocess
import sys
from unittest import mock

from bench_cells import ROOT, SPEC, fresh_table, run, small
from bench import check


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run([sys.executable, "-m", "bench.run", *args], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=240)


def test_cpu_is_refused_before_any_measurement():
    p = _cli(ROOT, "--workload", "sweep.suite", "--seed", str(2**31 + 3), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_benchmark_files_alone_are_refused(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    p = _cli(tmp_path, "--workload", "sweep.suite", "--seed", "1", "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_small_learned_run_is_correct_and_its_control_is_not():
    ok = run("ours.suite-125", seconds=2.0)
    assert ok["correct"], ok["checks"]
    assert ok["checks"]["pred_gap"]["value"] == 0.0  # the CPU computes float32 in full
    # the chip's control is float32 at `high`, which the CPU computes in
    # full float32; the rehearsal states the next lower precision it has
    with mock.patch.object(check, "control_prec", lambda cfg: "bfloat16"):
        control = run("ours.suite-125", seconds=2.0, control=True)
    assert not control["correct"]
    c = control["checks"]
    # the lower precision is caught by the predictor's numbers alone
    assert set(c) == {"pred_gap", "train_gap_ratio"}, c
    assert c["train_gap_ratio"]["value"] > c["train_gap_ratio"]["limit"], c


def test_small_sweep_control_is_not_correct():
    assert run("sweep.suite", seconds=1.0)["correct"]
    control = run("sweep.suite", seconds=1.0, control=True)
    assert not control["correct"] and control["checks"]["sim_mismatch"]["value"] > 0


def test_traced_windows_close_after_their_time():
    """A traced window is the mix's ``trace_seconds``: with none, the
    learned cell closes at its second round and the sweep after one call,
    and what ran is still checked and correct."""
    from bench.run import run_cell

    cfg, traffic = small("ours.vf-pairs-125", paper_predictor=False, scale=0.1, workloads=2)
    traffic["trace_seconds"] = 0.0
    with fresh_table():
        out = run_cell(SPEC, "ours.vf-pairs-125", 2**31 + 5, 1.0, True, cfg=cfg, traffic=traffic)
    c = out["extra"]["counters"]
    assert out["correct"], out["checks"]
    assert c["rounds"] == out["attempted"] == 1 and c["workload_runs"] == 0
    assert c["accesses"] == cfg["train"]["group_size"]
    assert out["extra"]["workload_s"][0][2] == 1

    cfg, traffic = small("sweep.suite", scale=0.1)
    traffic["trace_seconds"] = 0.0
    out = run_cell(SPEC, "sweep.suite", 2**31 + 5, 1.0, True, cfg=cfg, traffic=traffic)
    assert out["correct"] and out["extra"]["counters"]["calls"] == 1


def test_a_trace_that_lost_programs_is_taken_again():
    """Where the profiler lost device programs, the run traces another
    window and reads its metrics and counters from that one alone."""
    import bench.run as R

    read, complete = R._read_trace, iter([False, True])

    def first_trace_cut(*a):
        view = read(*a)
        view.modules = {"/device:TPU:0": []}
        lost = 0 if next(complete) else 1
        view.uncovered = lambda: lost
        return view

    cfg, traffic = small("sweep.suite", scale=0.1)
    traffic["trace_seconds"] = 0.0
    with mock.patch.object(R, "_read_trace", first_trace_cut):
        out = R.run_cell(SPEC, "sweep.suite", 2**31 + 5, 1.0, True, cfg=cfg, traffic=traffic)
    assert out["correct"] and out["extra"]["trace_attempts"] == 2 and out["extra"]["trace_uncovered"] == 0
    assert out["extra"]["counters"]["calls"] == out["attempted"] == 1
