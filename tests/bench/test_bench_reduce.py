"""The reduction from a profiler trace to per-layer numbers."""
import pytest

from bench_cells import ROOT  # noqa: F401  (puts the repository on the path)
from bench import reduce

MS = 1_000_000  # nanoseconds


def _view(**kw):
    # a 100 ms window; programs at [10, 30) and [20, 40) (overlapping),
    # [60, 70), and one after the window
    modules = {"/device:TPU:0": [(10 * MS, 30 * MS, "eval_scan"), (20 * MS, 40 * MS, "eval_scan"),
                                 (60 * MS, 70 * MS, "run_events"), (150 * MS, 160 * MS, "late")]}
    spans = [("window", 0, 100 * MS), ("manager.observe", 0, 48 * MS), ("trainer.evaluate", 5 * MS, 45 * MS),
             ("manager.feedback", 55 * MS, 95 * MS), ("simulator.run_segment", 56 * MS, 75 * MS)]
    kw.setdefault("synced", ("simulator.run_segment",))
    return reduce.View(modules, spans, **kw)


def test_busy_is_the_union_of_programs_inside_the_window():
    v = _view()
    assert v.window_s == pytest.approx(0.1)
    assert v.busy_s() == pytest.approx(0.040)  # [10,40) + [60,70); the late program is outside
    assert v.idle_share() == pytest.approx(60.0)


def test_idle_gaps_are_named_by_the_innermost_open_span():
    gaps = dict((round(s * 1e3), n) for n, s in _view().idle_gaps())
    # [0,10) under observe, [40,60) -> midpoint 50 is between observe and feedback,
    # [70,100) -> midpoint 85 inside feedback only
    assert gaps == {10: "trainer.evaluate", 20: "outside spans", 30: "manager.feedback"}


def test_program_attribution():
    v = _view()
    assert v.program_s(["eval_scan"]) == pytest.approx(0.030)  # a union: the overlap counts once
    assert v.program_s(["run_events", "apply_prefetch"]) == pytest.approx(0.010)
    assert v.program_s(["train_scan"]) is None  # never ran: nothing, not zero
    assert [n for n, _ in v.top_programs()] == ["eval_scan", "run_events"]
    assert v.top_programs()[0][1] == pytest.approx(0.040)  # per execution: 20 + 20 ms
    assert reduce.program_name("jit_eval_scan(123)") == "eval_scan"
    assert reduce.program_name("jit_run_events") == "run_events"


def test_self_time_leaves_out_children():
    v = _view()
    # observe 48 ms less evaluate 40 ms; feedback 40 ms less nothing named
    assert v.self_s(["manager.observe", "manager.feedback"], ["trainer.evaluate"]) == pytest.approx(0.048)


@pytest.mark.parametrize("kept", [
    lambda s, e, n: s < 50 * MS,  # the trace stops before the segment's program
    lambda s, e, n: n != "run_events",  # a hole where the segment's program ran
])
def test_a_trace_that_lost_programs_reads_nothing(kept):
    full = _view()
    modules = {d: [m for m in ms if kept(*m)] for d, ms in full.modules.items()}
    v = reduce.View(modules, full.spans, counters={"rounds": 2, "lane_events": 1000}, synced=full.synced)
    assert full.complete and full.uncovered() == 0
    assert not v.complete and v.uncovered() == 1
    assert v.idle_share() is None and v.program_s(["eval_scan"]) is None
    from bench import registry

    for name in ("trainer_device_ms_per_round", "segment_device_ms_per_round", "scan_device_ns_per_event",
                 "device_idle_share.learned"):
        assert registry.load_metric(name).read(v) is None, name


def test_no_device_events_reads_nothing():
    v = reduce.View({}, [("window", 0, MS)])
    assert v.idle_share() is None and v.busy_s() == 0.0 and v.idle_gaps() == []


def test_metric_readers_on_a_view():
    from bench import registry

    v = _view(counters={"rounds": 2, "lane_events": 1000, "eval_samples": 10, "train_steps": 1,
                        "lucir_steps": 1, "batch_size": 4,
                        "predictor": {"history": 10, "d_model": 16, "d_ff": 32, "num_layers": 1,
                                      "delta_vocab": 32}},
              peaks={"bf16_flops_per_s": 1e12})
    read = lambda n: registry.load_metric(n).read(v)
    assert read("trainer_device_ms_per_round") == pytest.approx(15.0)
    assert read("segment_device_ms_per_round") == pytest.approx(5.0)
    assert read("scan_device_ns_per_event") == pytest.approx(10_000.0)
    assert read("manager_ms_per_round") == pytest.approx(24.0)
    assert read("device_idle_share.learned") == pytest.approx(60.0)
    assert read("predictor_mfu") == pytest.approx(100 * 117_248 * (10 + 12 + 4) / 0.1 / 1e12)


RECORDED = ROOT / "tests" / "bench" / "data" / "small_v5e.xplane.pb"


def test_recorded_v5e_trace():
    """A window recorded on one TPU v5e (two 16-lane sweeps of AddVectors,
    one learned run of ATAX), cut to its program line and the benchmark's
    spans: the reduction reads it to the numbers it read on the chip."""
    raw = reduce.load(RECORDED)
    assert list(raw["modules"]) == ["/device:TPU:0"]
    v = reduce.View(raw["modules"], raw["spans"], counters={"rounds": 2}, chips=1,
                    synced=("simulator.run_segment", "simulator.run_batch"))
    assert v.complete
    assert v.window_s == pytest.approx(0.407277292, rel=1e-6)
    assert v.busy_s() == pytest.approx(0.135865244, rel=1e-6)
    assert v.idle_share() == pytest.approx(100 * (1 - 0.135865244 / 0.407277292), rel=1e-6)
    top = dict(v.top_programs())
    assert top["run_events"] == pytest.approx(0.100149767, rel=1e-6)
    assert top["train_scan"] == pytest.approx(0.032182723, rel=1e-6)
    assert top["eval_scan"] == pytest.approx(0.003063542, rel=1e-6)
    # the busy union never exceeds the programs' summed time, nor the window
    assert v.busy_s() <= sum(top.values()) + 1e-9 <= v.window_s
    names = {n for n, _, _ in raw["spans"]}
    assert {"window", "runtime.round", "manager.observe", "manager.feedback", "trainer.evaluate",
            "trainer.train_group", "simulator.run_segment", "simulator.run_batch"} <= names
    # every gap lies in the window and is named by a span or by none
    for name, secs in v.idle_gaps():
        assert 0 < secs < v.window_s and (name in names or name == "outside spans")
    manager = v.self_s(["manager.observe", "manager.feedback"], ["trainer.evaluate", "trainer.train_group"])
    assert 0 < manager < v.window_s


def test_recorded_v5e_trace_cut_short_is_refused():
    """The same trace with its device programs cut at the middle of the
    window, as the profiler cuts a long one."""
    raw = reduce.load(RECORDED)
    full = reduce.View(raw["modules"], raw["spans"], synced=("simulator.run_segment", "simulator.run_batch"))
    lo, hi = full.window
    cut = {d: [m for m in ms if m[1] < (lo + hi) / 2] for d, ms in raw["modules"].items()}
    v = reduce.View(cut, raw["spans"], synced=full.synced)
    assert v.uncovered() > 0 and v.idle_share() is None and v.program_s(["run_events"]) is None
