"""The program's spans beside the benchmark's (``bench.program_spans``)
and the per-layer metrics that read them."""
import shutil

import pytest

from bench_cells import ROOT  # noqa: F401  (puts the repository on the path)
from bench import program_spans, reduce, registry

MS = 1_000_000  # nanoseconds

EXISTING = ("manager_ms_per_round", "trainer_device_ms_per_round", "predictor_mfu", "segment_device_ms_per_round",
            "scan_device_ns_per_event", "device_idle_share.learned", "device_idle_share.sweep")
NEW = ("host_syncs_per_round", "trainer_host_ms_per_round", "segment_host_ms_per_round", "batch_host_ms_per_call")


def _bench_view(**kw):
    # a 100 ms window; programs at [10, 30) and [60, 70); the benchmark's spans around the calls
    modules = {"/device:TPU:0": [(10 * MS, 30 * MS, "eval_scan"), (60 * MS, 70 * MS, "run_events"),
                                 (150 * MS, 160 * MS, "late")]}
    spans = [("window", 0, 100 * MS), ("manager.observe", 0, 48 * MS), ("trainer.evaluate", 5 * MS, 45 * MS),
             ("manager.feedback", 55 * MS, 95 * MS), ("simulator.run_segment", 56 * MS, 80 * MS)]
    kw.setdefault("synced", ("simulator.run_segment",))
    kw.setdefault("counters", {"rounds": 2, "calls": 2, "lane_events": 1000, "eval_samples": 10, "train_steps": 1,
                               "lucir_steps": 1, "batch_size": 4,
                               "predictor": {"history": 10, "d_model": 16, "d_ff": 32, "num_layers": 1,
                                             "delta_vocab": 32}})
    kw.setdefault("peaks", {"bf16_flops_per_s": 1e12})
    return reduce.View(modules, spans, **kw)


def _program_spans(segment="simulator.run_segment"):
    p = lambda name, s, e, **ids: (name, s * MS, e * MS, ids)
    return [
        p("runtime.round", 0, 98, round=0), p("manager.observe", 1, 47), p("trainer.evaluate", 5, 45),
        p("trainer.stage", 4, 9), p("trainer.dispatch", 9, 10), p("sync.trainer.evaluate", 30, 33),
        p(segment, 56, 80), p("simulator.compress", 56, 58), p("simulator.stage", 58, 61),  # ends 1 ms into the scan
        p("sync.simulator.outs", 60, 70.5), p("sync.simulator.outs", 70.5, 71), p("sync.simulator.outs", 71, 71.5),
        p("simulator.unstage", 71.5, 72), p("simulator.decompress", 72, 79),
        p("sync.runtime.fault_count", 80, 81), p("trainer.train_group", 82, 94), p("trainer.stage", 82, 84),
        p("simulator.compress", 85, 88),  # outside the segment: read by no segment metric
        p("trainer.stage", 150, 160), p("sync.simulator.outs", 150, 151),  # after the window
    ]


def _program_view(segment="simulator.run_segment", **kw):
    v = _bench_view(**kw)
    return program_spans.ProgramView(v.modules, v.spans, v.counters, v.peaks, v.chips, v.synced,
                                     _program_spans(segment))


@pytest.mark.parametrize("name", EXISTING)
def test_existing_readers_read_the_same_beside_program_spans(name):
    read = registry.load_metric(name).read
    plain, with_program = read(_bench_view()), read(_program_view())
    assert plain is not None and with_program == plain


@pytest.mark.parametrize("name,want", [
    ("host_syncs_per_round", 5 / 2),  # one evaluate, three segment outputs, one fault clock in the window
    ("trainer_host_ms_per_round", (5 + 1 + 2) / 2),  # staging [4, 9) and [82, 84), dispatch [9, 10)
    # compress, stage (its idle part: [58, 60)), unstage, decompress inside the segment
    ("segment_host_ms_per_round", (2 + 2 + 0.5 + 7) / 2),
])
def test_learned_readers_on_a_view(name, want):
    assert registry.load_metric(name).read(_program_view()) == pytest.approx(want)


def test_batch_reader_on_a_view():
    read = registry.load_metric("batch_host_ms_per_call").read
    assert read(_program_view("simulator.run_batch")) == pytest.approx((2 + 2 + 0.5 + 7) / 2)
    assert read(_program_view()) is None  # no run_batch span: nothing, not zero


@pytest.mark.parametrize("name", NEW)
def test_new_readers_read_nothing_without_their_spans(name, tmp_path, monkeypatch):
    read = registry.load_metric(name).read
    v = _program_view()
    bare = program_spans.ProgramView(v.modules, v.spans, v.counters, v.peaks, v.chips, v.synced, [])
    assert read(bare) is None
    renamed = [("x." + n, s, e, ids) for n, s, e, ids in v.program_spans]
    assert read(program_spans.ProgramView(v.modules, v.spans, v.counters, v.peaks, v.chips, v.synced,
                                          renamed)) is None
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)  # no traced file to find the window in
    assert read(_bench_view()) is None


def test_host_stages_count_only_the_device_idle_time_in_them():
    v = _program_view()
    stages = ("simulator.compress", "simulator.stage", "simulator.unstage", "simulator.decompress")
    assert v.program_union_s(stages, ("simulator.run_segment",)) == pytest.approx(0.0125)  # [56, 61), [71.5, 79)
    assert v.idle_in_s(stages, ("simulator.run_segment",)) == pytest.approx(0.0115)  # less the scan's [60, 61)
    assert v.idle_in_s(["simulator.compress"]) == pytest.approx(0.005)  # inside the segment and after it
    assert v.idle_in_s(["simulator.compress"], ("simulator.run_batch",)) is None  # no such parent
    lost = {d: [m for m in mods if m[2] != "run_events"] for d, mods in v.modules.items()}
    cut = program_spans.ProgramView(lost, v.spans, v.counters, v.peaks, v.chips, v.synced,
                                    v.program_spans)  # the segment's scan lost from the trace
    assert not cut.complete and cut.idle_in_s(stages) is None


def test_program_count_reads_a_true_zero():
    v = _program_view()
    assert v.program_count("sync.") == 5 and v.program_count("simulator.rerun") == 0
    bare = program_spans.ProgramView(v.modules, v.spans, v.counters, v.peaks, v.chips, v.synced, [])
    assert bare.program_count("simulator.rerun") is None  # no program spans at all: a program without them


def test_gaps_are_named_by_the_innermost_span_of_either_list():
    at = lambda gaps: {round(start * 1e3): (name, round(secs * 1e3)) for name, secs, start in gaps}
    # the idle stretches [0, 10), [30, 60) and [70, 100), named at their midpoints 5, 45 and 85
    assert at(_bench_view().idle_gaps(with_start=True)) == {
        0: ("trainer.evaluate", 10), 30: ("trainer.evaluate", 30), 70: ("manager.feedback", 30)}
    assert at(_program_view().idle_gaps(with_start=True)) == {
        0: ("trainer.stage", 10), 30: ("trainer.evaluate", 30), 70: ("simulator.compress", 30)}
    assert [n for n, _ in _program_view().idle_gaps(n=2)] == ["trainer.evaluate", "simulator.compress"]


def test_idle_split_covers_the_idle_time():
    v = _program_view()
    split = v.idle_split()
    assert sum(split.values()) == pytest.approx(v.window_s - v.busy_s())
    assert split["trainer.stage"] == pytest.approx(0.005 + 0.002)  # [4, 9) and [82, 84)
    assert split["simulator.compress"] == pytest.approx(0.002 + 0.003)  # [56, 58) and [85, 88)
    assert split[program_spans.OUTSIDE] == pytest.approx(0.002)  # [98, 100)
    assert split["sync.simulator.outs"] == pytest.approx(0.0015)  # the copies after the program ends, [70, 71.5)


RECORDED = ROOT / "tests" / "bench" / "data" / "small_v5e_program.xplane.pb"
SYNCED = ("simulator.run_segment", "simulator.run_batch")


def test_recorded_trace_keeps_program_spans_apart():
    """A window recorded on one TPU v5e (two 16-lane sweeps of AddVectors,
    one learned run of two ATAX rounds), cut to its program line and the
    benchmark's and the program's spans: the program's spans load into a
    list of their own, and the device readings stay as they were."""
    raw = reduce.load(RECORDED)
    spans = program_spans.load(RECORDED)
    names = {p[0] for p in spans}
    assert {"runtime.round", "manager.observe", "trainer.stage", "trainer.dispatch", "simulator.compress",
            "simulator.stage", "simulator.dispatch", "simulator.unstage", "simulator.decompress",
            "sync.trainer.evaluate", "sync.simulator.outs", "sync.simulator.counters"} <= names
    assert {n for n, _, _ in raw["spans"]} == {"window", "simulator.run_batch", "runtime.new_manager"}
    assert [ids for n, _, _, ids in spans if n == "runtime.round"] == [{"round": 0}, {"round": 1}]
    plain = reduce.View(raw["modules"], raw["spans"], counters={"rounds": 2}, synced=SYNCED)
    v = program_spans.ProgramView(raw["modules"], raw["spans"], {"rounds": 2}, synced=SYNCED, program_spans=spans)
    assert v.complete and v.window_s == pytest.approx(0.646554551, rel=1e-6)
    assert v.busy_s() == plain.busy_s() == pytest.approx(0.184600521, rel=1e-6)
    assert v.top_programs() == plain.top_programs()
    assert sum(v.idle_split().values()) == pytest.approx(v.window_s - v.busy_s(), rel=1e-9)
    # the longest gap lies in the manager's classifier, where the benchmark's spans name nothing
    assert v.idle_gaps(1)[0][0] == "manager.classify" and plain.idle_gaps(1)[0][0] == "outside spans"


def test_recorded_trace_reads_the_new_metrics(tmp_path, monkeypatch):
    """The traced window's file is found again by its window span, and the
    four readers read it to the numbers the file holds."""
    shutil.copy(RECORDED, tmp_path / "vm.xplane.pb")
    monkeypatch.setattr(program_spans, "TRACE_DIR", tmp_path)
    raw = reduce.load(RECORDED)
    v = reduce.View(raw["modules"], raw["spans"], counters={"rounds": 2, "calls": 2}, synced=SYNCED)
    got = {n: registry.load_metric(n).read(v) for n in NEW}
    assert got == {
        "host_syncs_per_round": 19 / 2,  # both sweeps' pulls are in the window too
        "trainer_host_ms_per_round": pytest.approx(8.379644, rel=1e-6),
        "segment_host_ms_per_round": pytest.approx(22.313531, rel=1e-6),
        "batch_host_ms_per_call": pytest.approx(184.405521, rel=1e-6),
    }
