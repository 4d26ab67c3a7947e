"""Simulator hot-path throughput benchmark (ISSUEs 1 + 2 + 4).

Measures, per suite benchmark:
  * cold (compile-inclusive) and warm single-cell wall clock + accesses/sec
  * a 16-cell vmapped policy/prefetch/oversubscription sweep (run_batch)
    wall clock + aggregate cell-accesses/sec
  * the event-compression ratio actually achieved on the trace, for both
    plain run-length (`compress_rle_x`) and period-p interleave-aware
    compression (`compress_x`, what run/run_batch actually use)

    PYTHONPATH=src python -m benchmarks.sim_perf            # full quick-scale sweep
    PYTHONPATH=src python -m benchmarks.sim_perf --smoke    # CI: 3 benchmarks + concurrent + sharded lane
    PYTHONPATH=src python -m benchmarks.sim_perf --manager  # manager section: vectorized vs loop freq table
    PYTHONPATH=src python -m benchmarks.sim_perf --kernels  # kernels section: Pallas vs scan/host paths
    PYTHONPATH=src python -m benchmarks.sim_perf --update-baseline  # rewrite BENCH_sim.json "after"

``--manager`` prepends the streaming-manager section to the requested
run: the vectorized `PredictionFrequencyTable.update/dense` against the
frozen per-block loop (`LoopPredictionFrequencyTable`) on real benchmark
block streams, asserting identical table state and a real speedup;
combined with ``--update-baseline`` it records before/after into
BENCH_sim.json under ``manager``.

``--kernels`` prepends the Pallas-kernel section (ISSUE 10): the
victim-selection kernel path (``REPRO_SIM_KERNELS=1``) against the
default scan path over the full sweep grid — counters must stay
bit-identical (hard gate) — plus `PallasPredictionFrequencyTable`
against the host table on the same block streams.  On CPU hosts the
kernels run in interpret mode, so the ratio gate is a regression bound;
compiled-backend numbers are recorded into BENCH_sim.json as pending.

Output: experiments/bench/sim_perf.csv (+ the `name,us_per_call,derived`
contract line) and a comparison against the committed BENCH_sim.json
baseline so later PRs can track the trajectory.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import emit
from repro.uvm import simulator as S
from repro.uvm import trace as T
from repro.uvm.sweeps import EQUIV_CELLS as SWEEP_CELLS
from repro.uvm.sweeps import run_batch_forced_devices

BASELINE_PATH = Path(__file__).resolve().parent.parent / "BENCH_sim.json"


def bench_one(tr: T.Trace, name: str | None = None) -> dict:
    name = name or tr.name
    n = len(tr)
    blocks = tr.block.astype(np.int32)
    ev_rle = S.compress_events(blocks, S.next_use_for(tr))
    ev = S.compress_events(blocks, S.next_use_for(tr), periodic=True)

    t0 = time.time()
    S.run(tr, policy="lru", prefetch="tree")
    cold_s = time.time() - t0

    t0 = time.time()
    reps = 3
    for _ in range(reps):
        S.run(tr, policy="lru", prefetch="tree")
    warm_s = (time.time() - t0) / reps

    t0 = time.time()
    S.run_batch(tr, SWEEP_CELLS)
    sweep_s = time.time() - t0

    return {
        "benchmark": name,
        "accesses": n,
        "events": len(ev.blk),
        "events_rle": len(ev_rle.blk),
        "compress_x": round(n / max(len(ev.blk), 1), 2),
        "compress_rle_x": round(n / max(len(ev_rle.blk), 1), 2),
        "cold_s": round(cold_s, 3),
        "warm_s": round(warm_s, 4),
        "warm_acc_per_s": int(n / max(warm_s, 1e-9)),
        "sweep16_s": round(sweep_s, 3),
        "sweep_cell_acc_per_s": int(len(SWEEP_CELLS) * n / max(sweep_s, 1e-9)),
    }


def _suite_trace(name: str, scale: float, cap: int) -> T.Trace:
    tr = T.get_trace(name, scale=scale)
    return tr.slice(0, min(len(tr), cap))


def _sharded_lane_check(scale: float, cap: int) -> None:
    """Run one run_batch sweep in a subprocess with 4 forced host devices:
    the lane-sharded path must produce the same counters as this process's
    (single-device) run. Counters are integer state — bit-equality holds.

    CPU backend only: on an accelerator the child could not reach the
    device this process holds, and forced host devices say nothing about
    chips — ``chip_smoke.py --chips 4`` checks the sharded sweep there."""
    if jax.default_backend() != "cpu":
        print("# sharded lane check skipped off the CPU backend (chip_smoke.py --chips 4 runs it)")
        return
    want = S.run_batch(_suite_trace("ATAX", scale, cap), SWEEP_CELLS)
    got = run_batch_forced_devices("ATAX", scale, cap)
    assert got == want, "sharded run_batch diverged from single-device counters"
    print("# sharded lane ok (4 host devices, counters bit-identical)")


def bench_manager(scale: float, cap: int) -> list[dict]:
    """The `--manager` section: vectorized vs loop frequency-table engine
    on real block streams (update + dense export per group, flush cadence
    every 3 groups), table state asserted identical."""
    from repro.core.policy import LoopPredictionFrequencyTable, PredictionFrequencyTable

    rows = []
    G = 1024
    for name in ("ATAX", "Hotspot", "StreamTriad"):
        tr = _suite_trace(name, scale, cap)
        blocks = tr.block.astype(np.int64)
        batches = [blocks[i : i + G] for i in range(0, len(blocks), G)]

        def drive(make):
            t = make()
            t0 = time.time()
            for i, b in enumerate(batches):
                t.update(b)
                t.dense(tr.n_blocks)
                if i % 3 == 2:
                    t.on_intervals(3)  # exercise the flush path
            return time.time() - t0, t

        loop_s, t_loop = drive(LoopPredictionFrequencyTable)
        vec_s, t_vec = drive(PredictionFrequencyTable)
        assert np.array_equal(t_loop.tags, t_vec.tags) and np.array_equal(t_loop.counters, t_vec.counters), name
        n = len(blocks)
        rows.append({
            "benchmark": f"freq_table:{name}",
            "blocks": n,
            "loop_s": round(loop_s, 4),
            "vec_s": round(vec_s, 4),
            "speedup_x": round(loop_s / max(vec_s, 1e-9), 1),
            "loop_blocks_per_s": int(n / max(loop_s, 1e-9)),
            "vec_blocks_per_s": int(n / max(vec_s, 1e-9)),
        })
    agg = {
        "benchmark": "MANAGER_AGGREGATE",
        "blocks": sum(r["blocks"] for r in rows),
        "loop_s": round(sum(r["loop_s"] for r in rows), 4),
        "vec_s": round(sum(r["vec_s"] for r in rows), 4),
        "speedup_x": round(sum(r["loop_s"] for r in rows) / max(sum(r["vec_s"] for r in rows), 1e-9), 1),
        "loop_blocks_per_s": int(np.mean([r["loop_blocks_per_s"] for r in rows])),
        "vec_blocks_per_s": int(np.mean([r["vec_blocks_per_s"] for r in rows])),
    }
    return [agg] + rows


def bench_kernels(scale: float, cap: int, smoke: bool = False) -> list[dict]:
    """The `--kernels` section (ISSUE 10): the Pallas victim-selection and
    frequency-table kernels against the scan/numpy default paths.

    Per benchmark: a full EQUIV_CELLS `run_batch` sweep on the scan path vs
    REPRO_SIM_KERNELS' kernel path (counters asserted bit-identical — the
    hard gate), and the manager's freq-table stream through the host table
    vs `PallasPredictionFrequencyTable` (state asserted identical).  On CPU
    backends the kernels run in INTERPRET mode, so the wall-clock ratio is
    a regression bound, not a win; compiled-path numbers are recorded as
    pending a TPU/GPU run (`mode` says which this was).
    """
    from repro.core.policy import PallasPredictionFrequencyTable, PredictionFrequencyTable
    from repro.kernels.freq_table import ops as ft_ops

    mode = "interpret" if ft_ops.default_interpret() else "compiled"
    rows = []
    G = 1024
    for name in (("ATAX",) if smoke else ("ATAX", "Hotspot", "StreamTriad")):
        tr = _suite_trace(name, scale, cap)
        n = len(tr)

        def sweep(kernels):
            t0 = time.time()
            out = S.run_batch(tr, SWEEP_CELLS, kernels=kernels)
            return time.time() - t0, out

        sweep(False), sweep(True)  # warm both compile caches
        scan_s, scan_out = sweep(False)
        kern_s, kern_out = sweep(True)
        assert scan_out == kern_out, f"kernel path diverged from scan path on {name}"
        rows.append({
            "benchmark": f"evict_select:{name}",
            "mode": mode,
            "accesses": n,
            "scan_s": round(scan_s, 4),
            "kernel_s": round(kern_s, 4),
            "kernel_vs_scan_x": round(kern_s / max(scan_s, 1e-9), 2),
            "kernel_cell_acc_per_s": int(len(SWEEP_CELLS) * n / max(kern_s, 1e-9)),
        })

        blocks = tr.block.astype(np.int64)
        batches = [blocks[i : i + G] for i in range(0, len(blocks), G)]

        def drive(make):
            t = make()
            t0 = time.time()
            for i, b in enumerate(batches):
                t.update(b)
                t.lookup_many(b[: G // 4])
                if i % 3 == 2:
                    t.on_intervals(3)
            return time.time() - t0, t

        drive(PallasPredictionFrequencyTable)  # warm the kernel compile cache
        host_s, t_host = drive(PredictionFrequencyTable)
        pall_s, t_pall = drive(PallasPredictionFrequencyTable)
        assert np.array_equal(t_host.tags, t_pall.tags) and np.array_equal(
            t_host.counters, t_pall.counters), f"pallas freq table diverged on {name}"
        rows.append({
            "benchmark": f"freq_kernel:{name}",
            "mode": mode,
            "blocks": len(blocks),
            "host_s": round(host_s, 4),
            "kernel_s": round(pall_s, 4),
            "kernel_vs_host_x": round(pall_s / max(host_s, 1e-9), 2),
            "kernel_blocks_per_s": int(len(blocks) / max(pall_s, 1e-9)),
        })
    return rows


def bench_multi_tenant(scale: float, cap: int) -> dict:
    """The `--manager` section's multi-tenant row: one TenantMux (per-tenant
    pipelines, batched predictor dispatches) against one merged-stream
    manager on the SAME Section V-F concurrent trace — streaming protocol
    only (no simulator), SMOKE predictor, so the row isolates the mux's
    demux/dispatch overhead and records the top-1 win."""
    from repro.configs.predictor_paper import SMOKE
    from repro.core.incremental import TrainConfig
    from repro.uvm import runtime as R
    from repro.uvm.manager import FaultBatch, Outcomes

    parts = [_suite_trace(n, scale, cap) for n in ("StreamTriad", "Hotspot")]
    tr = T.concurrent(parts, seed=0, slice_len=512)
    tr = tr.slice(0, min(len(tr), 8000))  # bound the row's wall clock
    tcfg = TrainConfig(group_size=512, epochs=1, batch_size=128)

    def drive(multi_tenant: bool):
        mgr = (R.mux_for if multi_tenant else R.manager_for)(tr, SMOKE, tcfg)
        t0 = time.time()
        fc = 0
        for g0 in range(0, len(tr), tcfg.group_size):
            g1 = min(g0 + tcfg.group_size, len(tr))
            mgr.observe(FaultBatch(
                tr.page[g0:g1], tr.pc[g0:g1], tr.tb[g0:g1], tr.kernel[g0:g1],
                tenant=tr.tenant[g0:g1] if multi_tenant else None,
            ))
            fc += (g1 - g0) // 4  # a plausible far-fault rate for the clock
            mgr.feedback(Outcomes(fault_count=fc))
        return time.time() - t0, mgr.top1

    drive(False), drive(True)  # warm both paths' jit caches (fresh managers each drive)
    merged_s, merged_top1 = drive(False)
    mux_s, mux_top1 = drive(True)
    return {
        "benchmark": f"mux:{tr.name}",
        "accesses": len(tr),
        "merged_s": round(merged_s, 3),
        "mux_s": round(mux_s, 3),
        "overhead_x": round(mux_s / max(merged_s, 1e-9), 2),
        "merged_top1": round(merged_top1, 3),
        "mux_top1": round(mux_top1, 3),
        "mux_acc_per_s": int(len(tr) / max(mux_s, 1e-9)),
    }


def bench_qos(scale: float, cap: int) -> dict:
    """The `--manager` section's QoS row (PR 9): what budgeted capacity
    partitioning costs on the streaming path.  Drives the SAME concurrent
    trace through a plain TenantMux and a budgeted one (BudgetController:
    first-toucher block claims, per-round pressure scoring, elastic budget
    recompute, plus the per-round `evict_pref` sweep the runtime performs)
    — the controller is pure host-side numpy bookkeeping, so the warm
    overhead must stay well under 1.1x."""
    from repro.configs.predictor_paper import SMOKE
    from repro.core.incremental import TrainConfig
    from repro.uvm import runtime as R
    from repro.uvm.api import QosSpec, QosTierSpec
    from repro.uvm.manager import FaultBatch, Outcomes

    parts = [_suite_trace(n, scale, cap) for n in ("StreamTriad", "Hotspot")]
    tr = T.concurrent(parts, seed=0, slice_len=512)
    tr = tr.slice(0, min(len(tr), 8000))  # bound the row's wall clock
    tcfg = TrainConfig(group_size=512, epochs=1, batch_size=128)
    spec = QosSpec(tiers=(QosTierSpec("StreamTriad", floor=0.5, share=1.0),
                          QosTierSpec("Hotspot", floor=0.3, share=1.0)))

    def drive(budgeted: bool):
        mgr = R.mux_for(tr, SMOKE, tcfg, qos=spec if budgeted else None)
        # a plausible half-resident device, sized to the manager's padded
        # block bucket (what the runtime's simulator state hands evict_pref)
        resident = np.zeros(mgr.cfg.n_blocks, dtype=bool)
        resident[::2] = True
        t0 = time.time()
        fc = 0
        for g0 in range(0, len(tr), tcfg.group_size):
            g1 = min(g0 + tcfg.group_size, len(tr))
            mgr.observe(FaultBatch(
                tr.page[g0:g1], tr.pc[g0:g1], tr.tb[g0:g1], tr.kernel[g0:g1],
                tenant=tr.tenant[g0:g1],
            ))
            mgr.evict_pref(resident)  # the runtime calls this every group
            fc += (g1 - g0) // 4  # a plausible far-fault rate for the clock
            mgr.feedback(Outcomes(fault_count=fc))
        return time.time() - t0, mgr

    drive(False), drive(True)  # warm both paths' jit caches (fresh managers each drive)
    shared_s, _ = drive(False)
    qos_s, mgr = drive(True)
    assert mgr.qos is not None and mgr.qos.budgets, "budgeted drive produced no budgets"
    return {
        "benchmark": f"qos:{tr.name}",
        "accesses": len(tr),
        "shared_s": round(shared_s, 3),
        "qos_s": round(qos_s, 3),
        "overhead_x": round(qos_s / max(shared_s, 1e-9), 2),
        "budgets": {str(k): int(v) for k, v in mgr.qos.budgets.items()},
        "qos_acc_per_s": int(len(tr) / max(qos_s, 1e-9)),
    }


def bench_fault_tolerance(scale: float, cap: int) -> dict:
    """The `--manager` section's fault-tolerance row (PR 6): what resilience
    costs.  Times `state()` serialization, a SnapshotStore save/restore
    roundtrip (atomic publish + content-hash verify), and the degraded-mode
    observe path against the healthy learned path on the same stream — the
    degraded run wraps the trainer in a 100%-rate chaos fault so every
    round is served by the rule-based floor through the health machine."""
    import pickle
    import tempfile

    from repro.configs.predictor_paper import SMOKE
    from repro.core.incremental import TrainConfig
    from repro.uvm import runtime as R
    from repro.uvm.manager import (
        ChaosSchedule,
        FaultBatch,
        FaultInjector,
        HealthConfig,
        Outcomes,
        SnapshotStore,
    )

    tr = _suite_trace("ATAX", scale, cap)
    tr = tr.slice(0, min(len(tr), 8000))  # bound the row's wall clock
    tcfg = TrainConfig(group_size=512, epochs=1, batch_size=128)
    health = HealthConfig()

    def drive(chaos: bool):
        mgr = R.manager_for(tr, SMOKE, tcfg, health=health)
        if chaos:
            mgr.trainer = FaultInjector(
                ChaosSchedule(trainer_exc=1.0, seed=0)).wrap_trainer(mgr.trainer)
        t0 = time.time()
        fc = 0
        for g0 in range(0, len(tr), tcfg.group_size):
            g1 = min(g0 + tcfg.group_size, len(tr))
            mgr.observe(FaultBatch(tr.page[g0:g1], tr.pc[g0:g1], tr.tb[g0:g1], tr.kernel[g0:g1]))
            fc += (g1 - g0) // 4  # a plausible far-fault rate for the clock
            mgr.feedback(Outcomes(fault_count=fc))
        return time.time() - t0, mgr

    drive(False)  # warm the jit caches (fresh manager below)
    healthy_s, mgr = drive(False)
    degraded_s, chaos_mgr = drive(True)
    assert chaos_mgr.n_fallbacks > 0, "100%-rate trainer fault produced no fallback rounds"

    reps = 5
    t0 = time.time()
    for _ in range(reps):
        state = mgr.state()
    state_ms = (time.time() - t0) * 1000 / reps
    snapshot_bytes = len(pickle.dumps(state))

    with tempfile.TemporaryDirectory() as d:
        store = SnapshotStore(d)
        t0 = time.time()
        store.save(1, state)
        save_ms = (time.time() - t0) * 1000
        t0 = time.time()
        _, restored, _ = store.restore()
        restore_ms = (time.time() - t0) * 1000
    m2 = R.manager_for(tr, SMOKE, tcfg, health=health)
    m2.restore(restored)  # the roundtripped state must still load

    rounds = max(1, -(-len(tr) // tcfg.group_size))
    return {
        "benchmark": f"fault_tolerance:{tr.name}",
        "accesses": len(tr),
        "healthy_s": round(healthy_s, 3),
        "degraded_s": round(degraded_s, 3),
        "degraded_x": round(degraded_s / max(healthy_s, 1e-9), 2),
        "fallback_rounds": int(chaos_mgr.n_fallbacks),
        "rounds": rounds,
        "state_ms": round(state_ms, 2),
        "snapshot_bytes": snapshot_bytes,
        "save_ms": round(save_ms, 2),
        "restore_ms": round(restore_ms, 2),
    }


from repro.uvm.api.specs import SCALE_PRESETS, parse_scale  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true", help="3 benchmarks, sanity-gated (CI)")
    ap.add_argument("--scale", default="quick",
                    help="'quick' (0.4x, cap 6000), 'paper' (full generator sizes, cap 60000"
                         " — records wall clock into BENCH_sim.json), or a float")
    ap.add_argument("--cap", type=int, default=None, help="max trace length (overrides the scale preset)")
    ap.add_argument("--manager", action="store_true",
                    help="also run the manager section (vectorized vs loop frequency table);"
                         " with --update-baseline, record it into BENCH_sim.json")
    ap.add_argument("--kernels", action="store_true",
                    help="also run the kernels section (Pallas victim-select + freq-table"
                         " vs scan/host paths, bit-identity gated); with --update-baseline,"
                         " record it into BENCH_sim.json")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the committed BENCH_sim.json 'after' section")
    args = ap.parse_args(argv)
    args.scale, args.cap = parse_scale(args.scale, args.cap)
    paper_scale = (args.scale, args.cap) == SCALE_PRESETS["paper"]

    if args.manager:
        t0 = time.time()
        mrows = bench_manager(args.scale, args.cap)
        emit("sim_perf_manager", mrows, t0)
        t0 = time.time()
        mux_row = bench_multi_tenant(args.scale, args.cap)
        emit("sim_perf_manager_mux", [mux_row], t0)
        t0 = time.time()
        ft_row = bench_fault_tolerance(args.scale, args.cap)
        emit("sim_perf_manager_fault_tolerance", [ft_row], t0)
        t0 = time.time()
        qos_row = bench_qos(args.scale, args.cap)
        emit("sim_perf_manager_qos", [qos_row], t0)
        assert mrows[0]["speedup_x"] >= 2.0, mrows[0]  # vectorization must actually pay
        # the mux's demux + per-tenant dispatch overhead must stay modest
        # (it runs the SAME number of predictor samples, just partitioned)
        assert mux_row["overhead_x"] < 5.0, mux_row
        # the degraded floor skips the learned dispatch entirely, so an
        # all-faults run must not cost more than a small multiple of the
        # healthy run (recovery retries still dispatch-and-fail)
        assert ft_row["degraded_x"] < 5.0, ft_row
        # the budget controller is host-side numpy bookkeeping layered on
        # the same predictor dispatches — warm overhead must stay marginal
        assert qos_row["overhead_x"] < 1.1, qos_row
        # the committed record follows the file's convention: rewrite only
        # on an explicit --update-baseline, never from a routine/CI run
        if args.update_baseline and BASELINE_PATH.exists():
            base = json.loads(BASELINE_PATH.read_text())
            base["manager"] = {
                "freq_table_update": {
                    "before_loop": {k: mrows[0][k] for k in ("loop_s", "loop_blocks_per_s")},
                    "after_vectorized": {k: mrows[0][k] for k in ("vec_s", "vec_blocks_per_s", "speedup_x")},
                },
                "multi_tenant": mux_row,
                "fault_tolerance": ft_row,
                "qos": qos_row,
                "rows": mrows,
            }
            BASELINE_PATH.write_text(json.dumps(base, indent=2) + "\n")
            print(f"# recorded manager section into {BASELINE_PATH}")
        print("# manager section ok")
        # fall through: --manager ADDS the section to the requested run

    if args.kernels:
        t0 = time.time()
        krows = bench_kernels(args.scale, args.cap, smoke=args.smoke)
        evict_rows = [r for r in krows if r["benchmark"].startswith("evict_select:")]
        freq_rows = [r for r in krows if r["benchmark"].startswith("freq_kernel:")]
        emit("sim_perf_kernels_evict", evict_rows, t0)
        emit("sim_perf_kernels_freq", freq_rows, t0)
        # Bit-identity is asserted inside bench_kernels (the hard gate).
        # The wall-clock gates are regression bounds only.  evict_select
        # compares two jitted JAX paths, so its ratio is meaningful even
        # in interpret mode (~2x measured; bound 10x).  freq_kernel
        # compares against the pure-numpy host table, which interpret
        # mode cannot touch (per-block Python dispatch) — that ratio is
        # gated only on a compiled backend and recorded otherwise.
        for r in evict_rows:
            assert r["kernel_vs_scan_x"] < 10.0, r
        if krows[0]["mode"] == "compiled":
            for r in freq_rows:
                assert r["kernel_vs_host_x"] < 10.0, r
        if args.update_baseline and BASELINE_PATH.exists():
            base = json.loads(BASELINE_PATH.read_text())
            base["kernels"] = {
                "mode": krows[0]["mode"],
                "bit_identical_to_scan_path": True,
                "compiled_backend": ("recorded" if krows[0]["mode"] == "compiled"
                                     else "pending (CPU-only host: interpret mode)"),
                "rows": krows,
            }
            BASELINE_PATH.write_text(json.dumps(base, indent=2) + "\n")
            print(f"# recorded kernels section into {BASELINE_PATH}")
        print("# kernels section ok")
        # fall through: --kernels ADDS the section to the requested run

    names = ["ATAX", "Hotspot", "StreamTriad"] if args.smoke else list(T.BENCHMARKS)
    t0 = time.time()
    rows = [bench_one(_suite_trace(n, args.scale, args.cap)) for n in names]
    # Section V-F multi-tenant cell: two pattern classes interleaved at
    # scheduler-slice granularity in disjoint page ranges
    conc = T.concurrent(
        [_suite_trace("StreamTriad", args.scale, args.cap), _suite_trace("Hotspot", args.scale, args.cap)]
    )
    rows.append(bench_one(conc, name=f"concurrent:{conc.name}"))
    agg = {
        "benchmark": "AGGREGATE",
        "accesses": sum(r["accesses"] for r in rows),
        "events": sum(r["events"] for r in rows),
        "events_rle": sum(r["events_rle"] for r in rows),
        "compress_x": round(sum(r["accesses"] for r in rows) / max(sum(r["events"] for r in rows), 1), 2),
        "compress_rle_x": round(sum(r["accesses"] for r in rows) / max(sum(r["events_rle"] for r in rows), 1), 2),
        "cold_s": round(sum(r["cold_s"] for r in rows), 3),
        "warm_s": round(sum(r["warm_s"] for r in rows), 4),
        "warm_acc_per_s": int(np.mean([r["warm_acc_per_s"] for r in rows])),
        "sweep16_s": round(sum(r["sweep16_s"] for r in rows), 3),
        "sweep_cell_acc_per_s": int(np.mean([r["sweep_cell_acc_per_s"] for r in rows])),
    }
    rows.insert(0, {**agg, "derived": f"warm_{agg['warm_acc_per_s']}acc/s"})
    emit("sim_perf", rows, t0)

    if BASELINE_PATH.exists():
        base = json.loads(BASELINE_PATH.read_text())
        before = base.get("before", {}).get("table1_table6_quick_s")
        after = base.get("after", {}).get("table1_table6_quick_s")
        if before and after:
            print(f"# committed baseline: table1+table6 quick {before}s -> {after}s "
                  f"({before / after:.1f}x); this run's sweep throughput above")
        if paper_scale and not args.smoke:
            # the ROADMAP follow-up: paper-scale wall clock tracked alongside
            # the quick-suite trajectory (full generator sizes, cap 60000)
            base["paper_scale"] = {
                "suite_total_s": round(time.time() - t0, 1),
                "aggregate": agg,
                "rows": rows[1:],  # per-benchmark (AGGREGATE row is `aggregate`)
            }
            BASELINE_PATH.write_text(json.dumps(base, indent=2) + "\n")
            print(f"# recorded paper-scale wall clock into {BASELINE_PATH}")
        if args.update_baseline:
            base.setdefault("after", {})["sim_perf_rows"] = rows
            BASELINE_PATH.write_text(json.dumps(base, indent=2) + "\n")
            print(f"# updated {BASELINE_PATH}")

    if args.smoke:
        # CI sanity gates: event compression must actually engage on the
        # smoke set (compress_x == 1.0 would mean it is disabled), period-p
        # compression must beat plain RLE on the streaming benchmark
        # (StreamTriad: RLE 1.0x vs periodic >= 3x — the ISSUE 2 target),
        # and the warm path must beat one access per millisecond
        assert agg["compress_x"] >= 1.5, agg
        stream = next(r for r in rows if r["benchmark"] == "StreamTriad")
        assert stream["compress_x"] >= 3.0 > stream["compress_rle_x"], stream
        assert agg["warm_acc_per_s"] > 10_000, agg
        _sharded_lane_check(args.scale, args.cap)
        print("# smoke ok")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
