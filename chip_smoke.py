"""Smoke run of the learned oversubscription manager on a TPU.

Drives the paper's main path through its user entry points, at the
geometry ``Session.paper()`` uses: the full-width page predictor
(``configs/predictor_paper.CONFIG``), traces at scale 1.0 capped at
60,000 accesses, and ``PAPER_TRAIN``.  Every phase is checked against a
reference and prints one JSON line:

* ``device``: the first JAX device is a TPU, else exit 1 before any
  other work.
* ``sweep``: ``simulator.run_batch`` over the 16 ``EQUIV_CELLS`` on
  Hotspot and AddVectors, on the scan path and on the Pallas kernel path.
  The counters must match each other and the scan path on the host's CPU
  device, bit for bit.  The kernel path must be compiled Mosaic code
  (``tpu_custom_call``), not interpret mode.
* ``learned``: ``runtime.run_ours`` at 125% oversubscription with the
  Section V-A pretrained table, on Srad-v2 and on the Section V-F pair
  StreamTriad+Hotspot through ``TenantMux``.  One ``Trainer.evaluate``
  and one ``train_group`` call are compared with the same calls on the
  CPU device.
* ``freq_table``: the compiled ``PallasPredictionFrequencyTable`` against
  ``PredictionFrequencyTable``, replaying the updates and flushes that
  the cold learned runs made to their frequency tables.

``--chips 4`` runs only the lane-sharded paths instead: ``run_batch``
with lanes over four chips against one device and against the kernel
path (which keeps its lanes on one device), and ``run_ours_many``
against serial ``run_ours``.

Times are wall-clock seconds of this run (``cold`` includes compiles).
No result comes from the run store.  The last line is
``{"ok": true, "device": {...}}``, printed only when every phase passed.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips, sharded phase only
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent

#: Trainer.evaluate, TPU against the CPU device: the least share of
#: predicted class ids that must agree.  TPU float32 matmuls run at reduced
#: (bfloat16-pass) precision by default; that can flip an argmax only where
#: two logits nearly tie, which a trained predictor does for few samples.
EVAL_AGREE_MIN = 0.99
#: train_group, TPU against the CPU device: the largest relative L2 distance
#: between the two parameter updates.  The same reduced-precision matmuls
#: perturb each gradient by about 1e-2 relative; Adam passes that through
#: to the update, and over one group's 24 steps it must stay well below 5%.
TRAIN_REL_TOL = 0.05
#: run_ours_many (vmapped lanes) against serial run_ours on the same chip:
#: the largest absolute top-1 difference.  A batched matmul may round
#: differently from a single one, but flipping 1% of predictions would
#: mean the lanes run a different model.
TOP1_TOL = 0.01

#: the benchmarks of the --chips 4 learned-lanes comparison (paper focus
#: set less Hotspot, whose 26 groups would only lengthen the serial tail)
SHARDED_OURS = ("ATAX", "BICG", "NW", "Srad-v2")


def emit(phase: str, t0: float, failures: list, **fields) -> bool:
    """Print the phase's JSON line; True when all of its checks passed."""
    rec = {"phase": phase, "passed": not failures, "wall_s": time.perf_counter() - t0, **fields}
    if failures:
        rec["failed"] = failures
    print(json.dumps(rec), flush=True)
    return not failures


def timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, time.perf_counter() - t0


def phase_device(chips: int) -> dict | None:
    """The device JAX reports, or None when it is not what was asked for."""
    import jax

    t0 = time.perf_counter()
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": len(devs)}
    failures = []
    if info["platform"] != "tpu":
        failures.append("the first device is not a TPU")
    elif len(devs) != chips:
        failures.append(f"{chips} chips asked for, {len(devs)} found")
    return info if emit("device", t0, failures, **info) else None


def kernel_scan_text(tr, lanes: int) -> str:
    """Compiled text of the kernel-path event scan that ``run_batch`` runs
    for ``tr`` over ``lanes`` lanes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.uvm import simulator as S

    ev = S._pad_events(S.compress_events(tr.block.astype(np.int32), S.next_use_for(tr), periodic=True))
    states = jax.eval_shape(lambda: S._stack_states([S.init_state(S.bucket_blocks(tr.n_blocks))] * lanes))
    events = [jax.ShapeDtypeStruct(ev.blk.shape, jnp.int32)] * 5
    cells = [jax.ShapeDtypeStruct((lanes,), jnp.int32)] * 4
    return S._jits(kernels=True)[0].lower(states, *events, *cells).compile().as_text()


def phase_sweep(session) -> bool:
    import jax

    from repro.kernels.freq_table import ops as ft_ops
    from repro.uvm import simulator as S
    from repro.uvm.sweeps import EQUIV_CELLS

    t0 = time.perf_counter()
    failures = []
    if S._kernel_interpret() or ft_ops.default_interpret():
        failures.append("the Pallas kernels would run in interpret mode")
    cpu = jax.devices("cpu")[0]
    traces = {}
    for name in ("Hotspot", "AddVectors"):
        tr = session.trace(name)
        row = {"accesses": len(tr), "block_bucket": S.bucket_blocks(tr.n_blocks)}
        scan, row["scan_cold_s"] = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=False))
        _, row["scan_warm_s"] = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=False))
        kern, row["kernel_cold_s"] = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=True))
        _, row["kernel_warm_s"] = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=True))
        with jax.default_device(cpu):
            host, row["cpu_device_s"] = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=False))
        row["kernel_equals_scan"] = kern == scan
        row["tpu_equals_cpu"] = scan == host
        row["tpu_custom_call"] = "tpu_custom_call" in kernel_scan_text(tr, len(EQUIV_CELLS))
        row["pages_thrashed"] = [c["pages_thrashed"] for c in scan]
        for check in ("kernel_equals_scan", "tpu_equals_cpu", "tpu_custom_call"):
            if not row[check]:
                failures.append(f"{name}: {check}")
        traces[name] = row
    return emit("sweep", t0, failures, cells=len(EQUIV_CELLS), traces=traces)


def compare_trainer(session, table) -> dict:
    """One Trainer.evaluate and one train_group call on the first Srad-v2
    group, on the TPU and on the CPU device, from the same params."""
    import jax
    import numpy as np

    from repro.core.features import DeltaVocab, FeatureStream
    from repro.core.incremental import Trainer
    from repro.core.model_table import Entry
    from repro.core.pattern import PatternClassifier

    tr = session.trace("Srad-v2")
    pcfg, tcfg = session.pcfg, session.tcfg
    g = tcfg.group_size
    vocab = DeltaVocab(pcfg.delta_vocab)
    fs = FeatureStream(tr, vocab, pcfg.history, page_vocab=pcfg.page_vocab,
                       pc_vocab=pcfg.pc_vocab, tb_vocab=pcfg.tb_vocab).windows(0, g)
    n_active = max(vocab.n_classes, 2)
    params = table.get(PatternClassifier().classify(tr.block[:g], tr.kernel[:g])).params
    trainer = Trainer(pcfg, tcfg)

    def on(dev):
        with jax.default_device(dev):
            p = jax.device_put(params, dev)
            correct, pred = trainer.evaluate(p, fs, n_active)
            new = trainer.train_group(Entry(params=p), fs, n_active).params
            delta = jax.tree.leaves(jax.tree.map(
                lambda a, b: np.asarray(a, np.float64) - np.asarray(b, np.float64), new, p))
        return correct, pred, delta

    c_tpu, p_tpu, d_tpu = on(jax.devices()[0])
    c_cpu, p_cpu, d_cpu = on(jax.devices("cpu")[0])
    diff = math.sqrt(sum(float(np.sum((a - b) ** 2)) for a, b in zip(d_tpu, d_cpu)))
    norm = math.sqrt(sum(float(np.sum(b ** 2)) for b in d_cpu))
    return {
        "samples": len(fs),
        "eval_top1_tpu": float(c_tpu.mean()),
        "eval_top1_cpu": float(c_cpu.mean()),
        "eval_pred_agree": float(np.mean(p_tpu == p_cpu)),
        "train_update_rel_l2": diff / norm,
    }


@contextlib.contextmanager
def record_table_traffic():
    """Log every ``PredictionFrequencyTable`` update and flush made inside
    the block, in order, one log per table (each tenant of a ``TenantMux``
    owns one): the stream the manager really fed its tables."""
    import numpy as np

    from repro.core.policy import PredictionFrequencyTable as Table

    logs, update, flush = {}, Table.update, Table.on_intervals

    def logged_update(self, blocks):
        logs.setdefault(id(self), []).append(("update", np.array(blocks, np.int64).ravel()))
        return update(self, blocks)

    def logged_flush(self, n):
        logs.setdefault(id(self), []).append(("flush", n))
        return flush(self, n)

    with mock.patch.object(Table, "update", logged_update), \
            mock.patch.object(Table, "on_intervals", logged_flush):
        yield logs


def phase_learned(session):
    """Whether the phase passed, and the frequency-table traffic of its
    cold runs for ``phase_freq_table``."""
    from repro.uvm import runtime as R
    from repro.uvm import simulator as S

    t0 = time.perf_counter()
    failures = []
    memos = set(R.PRETRAIN_CACHE_DIR.glob("pretrain_*.pkl"))
    table, pretrain_s = timed(session.pretrained)
    memo_hit = not set(R.PRETRAIN_CACHE_DIR.glob("pretrain_*.pkl")) - memos
    runs, traffic = {}, []
    workloads = {"Srad-v2": session.workload("Srad-v2"),
                 "StreamTriad+Hotspot": session.concurrent(("StreamTriad", "Hotspot"))}
    for label, w in workloads.items():
        tr = session.trace(w)
        mgr = session.manager(w, 1.25, pretrained=True)
        with record_table_traffic() as logs:  # cold: compiles included
            res, cold_s = timed(lambda: R.run_ours(tr, manager=mgr))
        traffic.extend(logs.values())
        warm_mgr = session.manager(w, 1.25, pretrained=True)
        res_warm, warm_s = timed(lambda: R.run_ours(tr, manager=warm_mgr))
        base = S.run(tr, policy="lru", prefetch="tree", oversubscription=1.25)
        row = {
            "accesses": len(tr), "manager": type(mgr).__name__,
            "cold_s": cold_s, "warm_s": warm_s,
            "top1": res.top1, "n_predictions": res.n_predictions,
            "pages_thrashed": res.stats["pages_thrashed"],
            "lru_tree_pages_thrashed": base.pages_thrashed,
            "fallback_rounds": mgr.n_fallbacks,
        }
        if mgr.cfg.health is not None:
            failures.append(f"{label}: the health machine is on")
        if mgr.n_fallbacks:
            failures.append(f"{label}: rounds went to the rule-based fallback")
        if not res.n_predictions > 0:
            failures.append(f"{label}: no predictions")
        if not (math.isfinite(res.top1) and 0.0 <= res.top1 <= 1.0):
            failures.append(f"{label}: top1 {res.top1} outside [0, 1]")
        if res_warm != res:
            failures.append(f"{label}: two runs on the chip disagree")
        runs[label] = row
    trainer = compare_trainer(session, table)
    if trainer["eval_pred_agree"] < EVAL_AGREE_MIN:
        failures.append(f"evaluate: TPU and CPU predictions agree on {trainer['eval_pred_agree']}")
    if not trainer["train_update_rel_l2"] <= TRAIN_REL_TOL:
        failures.append(f"train_group: TPU and CPU updates differ by {trainer['train_update_rel_l2']}")
    ok = emit("learned", t0, failures, pretrain_memo_hit=memo_hit, pretrain_s=pretrain_s,
         runs=runs, trainer_vs_cpu=trainer,
         tolerances={"eval_pred_agree_min": EVAL_AGREE_MIN, "train_update_rel_l2_max": TRAIN_REL_TOL})
    return ok, traffic


def phase_freq_table(traffic) -> bool:
    """Replay each recorded table stream through the compiled Pallas table
    and the numpy table: every lookup, and the tags and counters after
    every call, must be identical."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.policy import PallasPredictionFrequencyTable, PredictionFrequencyTable
    from repro.kernels.freq_table import kernel, ops

    t0 = time.perf_counter()
    failures = []
    if ops.default_interpret():
        failures.append("the freq_table kernels would run in interpret mode")
    shape = PredictionFrequencyTable().tags.shape
    shapes = [jax.ShapeDtypeStruct(shape, jnp.int32)] * 2 + [jax.ShapeDtypeStruct((2048,), jnp.int32)]
    for fn in (kernel.freq_update, kernel.freq_lookup):
        if "tpu_custom_call" not in fn.lower(*shapes, interpret=ops.default_interpret()).compile().as_text():
            failures.append(f"{fn.__name__} is not a Mosaic kernel")
    updates = [b for log in traffic for op, b in log if op == "update"]
    if not updates:
        failures.append("the learned runs fed their frequency tables nothing")
    host_s = pallas_s = 0.0
    for t, log in enumerate(traffic):
        host, pallas = PredictionFrequencyTable(), PallasPredictionFrequencyTable()
        for i, (op, arg) in enumerate(log):
            if op == "flush":
                host.on_intervals(arg)
                pallas.on_intervals(arg)
                continue
            want, s = timed(lambda: (host.update(arg), host.lookup_many(arg))[1])
            host_s += s
            got, s = timed(lambda: (pallas.update(arg), pallas.lookup_many(arg))[1])
            pallas_s += s
            if not (np.array_equal(want, got) and np.array_equal(host.tags, pallas.tags)
                    and np.array_equal(host.counters, pallas.counters)):
                failures.append(f"table {t}, call {i}: lookups, tags or counters differ")
    return emit("freq_table", t0, failures, tables=len(traffic), updates=len(updates),
                blocks=int(sum(len(b) for b in updates)), max_update_blocks=max(map(len, updates), default=0),
                host_s=host_s, pallas_s=pallas_s, pallas_includes_compile=True)


def phase_sharded(session) -> bool:
    """The lane-sharded sweeps over four chips against one device."""
    from repro.distributed.compat import lane_shardings
    from repro.uvm import runtime as R
    from repro.uvm import simulator as S
    from repro.uvm.sweeps import EQUIV_CELLS

    t0 = time.perf_counter()
    failures = []
    if lane_shardings(len(EQUIV_CELLS))[0] is None or lane_shardings(len(SHARDED_OURS))[0] is None:
        failures.append("lanes are not sharded over the chips")
    sweeps = {}
    for name in ("Hotspot", "AddVectors"):
        tr = session.trace(name)
        sharded, sharded_s = timed(lambda: S.run_batch(tr, EQUIV_CELLS))
        with mock.patch.dict(os.environ, {"REPRO_SIM_SHARD": "0"}):
            one, one_s = timed(lambda: S.run_batch(tr, EQUIV_CELLS))
        # the compiled kernel path keeps its lanes on one device
        kern, kern_s = timed(lambda: S.run_batch(tr, EQUIV_CELLS, kernels=True))
        sweeps[name] = {"sharded_s": sharded_s, "one_device_s": one_s, "kernel_s": kern_s,
                        "sharded_equals_one_device": sharded == one, "kernel_equals_sharded": kern == sharded}
        if sharded != one:
            failures.append(f"{name}: sharded counters differ from one device")
        if kern != sharded:
            failures.append(f"{name}: kernel-path counters differ from the sharded scan")
    traces = [session.trace(n) for n in SHARDED_OURS]
    pcfg, tcfg = session.pcfg, session.tcfg
    batched, batched_s = timed(lambda: R.run_ours_many(traces, pcfg, tcfg))
    serial, serial_s = timed(lambda: [R.run_ours(t, pcfg, tcfg) for t in traces])
    lanes = {}
    for name, b, s in zip(SHARDED_OURS, batched, serial):
        lanes[name] = {"counters_equal": b.stats == s.stats, "top1_batched": b.top1, "top1_serial": s.top1}
        if b.stats != s.stats:
            failures.append(f"{name}: run_ours_many counters differ from run_ours")
        if not abs(b.top1 - s.top1) <= TOP1_TOL:
            failures.append(f"{name}: top1 {b.top1} batched against {s.top1} serial")
    return emit("sharded", t0, failures, sweeps=sweeps, ours_many_s=batched_s, ours_serial_s=serial_s,
         lanes=lanes, top1_tol=TOP1_TOL)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the lane-sharded phase, over four chips")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke.py: no repository around {ROOT}", file=sys.stderr)
        return 2
    info = phase_device(args.chips)
    if info is None:
        return 1

    os.chdir(ROOT)  # the pretrain memo and run store paths are checkout-relative
    os.environ["REPRO_RUN_STORE"] = "0"
    sys.path.insert(0, str(ROOT / "src"))
    from repro.uvm.api import RunStore, Session

    session = Session.paper(store=RunStore(enabled=False))
    if args.chips == 4:
        ok = phase_sharded(session)
    else:
        # every phase runs, so one call shows all that fails
        ok = phase_sweep(session)
        learned_ok, traffic = phase_learned(session)
        ok = phase_freq_table(traffic) and learned_ok and ok
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
