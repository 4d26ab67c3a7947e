"""Fault-stream server throughput: microbatched vs serial dispatch.

Measures the :class:`repro.uvm.server.FaultStreamServer` serving N
concurrent loadgen clients that replay the SAME deterministic exported
fault log (identical lanes share one vmap shape bucket, the best case the
cross-connection :class:`~repro.uvm.server.core.MicrobatchDispatcher` is
built for).  Two timed passes over an in-process unix-socket server:

* ``serial``  — ``microbatch=False``: per-connection dispatch, every
  session-tick is its own executor task + event-loop round-trip
  (dispatch-equivalent to N independent ``cli serve`` processes sharing
  warm jits — ``ticks`` in the output counts those round-trips);
* ``batched`` — the default lockstep engine: staged halves from every
  connection gather into ONE worker hop per tick.  The tick executes
  per :func:`repro.uvm.server.core._resolve_engine`: ``vmap`` (one
  ``evaluate_many``/``train_group_many`` across lanes) on multi-device,
  ``fused`` (warm serial jits swept inside the single hop) on one
  device, where the repo's benched policy is that the vmapped path
  costs more than serial (see BENCH_sim.json notes).

Reported per mode: wall clock, sustained faults/sec, closed-loop action
latency p50/p99 (observe line sent -> action record received).  The runs
are content-deterministic, so the bench doubles as a scale-out
bit-identity gate: every client's action stream must be byte-identical
across the two modes (and across clients — same log, same seed).

Usage::

    PYTHONPATH=src python -m benchmarks.serve_perf --smoke      # CI gate
    PYTHONPATH=src python -m benchmarks.serve_perf              # full scale
    PYTHONPATH=src python -m benchmarks.serve_perf --aot        # + AOT section
    PYTHONPATH=src python -m benchmarks.serve_perf --update-baseline

``--smoke`` asserts the acceptance gates (>= 32 concurrent sessions, zero
errors, batched strictly beating serial); ``--update-baseline`` rewrites
the committed ``BENCH_serve.json``.  ``--aot`` adds the compile-once
section: three fresh subprocesses time a cold first round under plain jit,
under ``enable_aot`` against an empty cache (export cost), and against the
populated cache (reload skips tracing); the jit and reloaded action
records must match byte-for-byte.  The children run first, one at a time,
while this process has not yet touched a JAX device: an accelerator
belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import asyncio
import io
import json
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from benchmarks.common import emit  # noqa: F401 — also enables the XLA compile cache
from repro.configs.predictor_paper import SMOKE
from repro.core.incremental import TrainConfig, Trainer
from repro.uvm import trace as T
from repro.uvm.manager import HealthConfig, ManagerConfig
from repro.uvm.server import FaultStreamServer, ServerConfig
from repro.uvm.server.loadgen import make_connector, run_loadgen

BASELINE_PATH = Path(__file__).resolve().parent.parent / "BENCH_serve.json"

#: (n_clients, n_batches, pages_per_batch, timed_repeats)
SCALES = {"smoke": (32, 6, 192, 2), "full": (64, 10, 256, 2)}


def make_workload(n_batches: int, batch: int):
    """One deterministic exported fault log + the manager config that
    serves it (SMOKE predictor: the bench times dispatch, not the model)."""
    tr = T.get_trace("StreamTriad", scale=1.0).slice(0, n_batches * batch)
    buf = io.StringIO()
    T.to_fault_log(tr, buf, batch=batch)
    lines = [ln for ln in buf.getvalue().splitlines() if ln and not ln.startswith("#")]
    assert len(lines) == n_batches, (len(lines), n_batches)
    mcfg = ManagerConfig(
        predictor=SMOKE,
        train=TrainConfig(group_size=batch, epochs=1, batch_size=64),
        n_pages=int(tr.n_pages), n_blocks=64, capacity=48,
        health=HealthConfig(),
    )
    return lines, mcfg


async def _serve_once(trainer, mcfg, lines, n_clients: int, *, microbatch: bool,
                      sock_dir: str, gather_spins: int = 2):
    cfg = ServerConfig(manager=mcfg, microbatch=microbatch, gather_spins=gather_spins,
                       exec_mode="auto")
    server = FaultStreamServer(cfg, trainer=trainer)
    path = str(Path(sock_dir) / f"serve-{'b' if microbatch else 's'}.sock")
    await server.start(path=path)
    try:
        stats = await run_loadgen(make_connector(f"unix:{path}"), lines, n_clients)
    finally:
        await server.shutdown()
    return stats, server


def run_mode(trainer, mcfg, lines, n_clients: int, *, microbatch: bool,
             sock_dir: str, repeats: int):
    """One untimed warmup pass (absorbs residual jit traces for this
    mode's dispatch shapes) then ``repeats`` timed passes; keep the best."""
    asyncio.run(_serve_once(trainer, mcfg, lines, n_clients,
                            microbatch=microbatch, sock_dir=sock_dir))
    best = None
    for _ in range(repeats):
        stats, server = asyncio.run(_serve_once(
            trainer, mcfg, lines, n_clients, microbatch=microbatch, sock_dir=sock_dir))
        if best is None or stats.wall_s < best[0].wall_s:
            best = (stats, server)
    return best


def prewarm_lanes(trainer, mcfg, lines, n_clients: int, sock_dir: str) -> None:
    """Compile every vmap lane-width bucket a timed batched run can hit
    (only relevant when the batched engine resolves to ``vmap``).

    Lane groups pad to the next power of two (>= ``MIN_VMAP_LANES``), so a
    tick that gathers 5..8 sessions hits the 8-wide executable and so on —
    run a short untimed pass at each power-of-two client count up to
    ``n_clients`` so no timed tick pays a fresh trace."""
    from repro.uvm.server.core import _resolve_engine

    if _resolve_engine("auto") != "vmap":
        return
    warm_lines = lines[: min(3, len(lines))]
    width = Trainer.MIN_VMAP_LANES
    while width <= n_clients:
        asyncio.run(_serve_once(trainer, mcfg, warm_lines, width,
                                microbatch=True, sock_dir=sock_dir))
        width *= 2


def bench_serve(scale: str):
    n_clients, n_batches, batch, repeats = SCALES[scale]
    lines, mcfg = make_workload(n_batches, batch)
    trainer = Trainer(mcfg.predictor, mcfg.train, mcfg.kind)
    rows, streams = [], {}
    with tempfile.TemporaryDirectory() as sock_dir:
        prewarm_lanes(trainer, mcfg, lines, n_clients, sock_dir)
        for mode, microbatch in (("serial", False), ("batched", True)):
            stats, server = run_mode(trainer, mcfg, lines, n_clients,
                                     microbatch=microbatch, sock_dir=sock_dir,
                                     repeats=repeats)
            streams[mode] = [r.actions for r in stats.per_client]
            rows.append({
                "mode": mode,
                "engine": server.dispatcher.engine if microbatch else "per-conn",
                "clients": stats.clients,
                "actions": stats.actions,
                "errors": stats.errors,
                "wall_s": round(stats.wall_s, 4),
                "faults_per_s": round(stats.faults_per_s, 1),
                "p50_ms": round(stats.p50_ms, 3),
                "p99_ms": round(stats.p99_ms, 3),
                "ticks": server.dispatcher.n_ticks,
                "max_eval_lanes": server.dispatcher.max_eval_lanes,
            })
    serial, batched = rows[0], rows[1]
    speedup = serial["wall_s"] / batched["wall_s"] if batched["wall_s"] > 0 else 0.0
    for r in rows:
        r["speedup_x"] = round(speedup, 3)
        r["derived"] = f"batched/serial speedup {speedup:.2f}x"
    # scale-out bit-identity: same log + same seeds => every client's
    # action stream is byte-identical across modes (and across clients)
    assert streams["serial"] == streams["batched"], "mode streams diverged"
    flat = [s for per_mode in streams.values() for s in per_mode]
    assert all(s == flat[0] for s in flat), "client streams diverged"
    return rows


# --- AOT section: compile-once export vs per-process jit tracing ------------

def _aot_child(mode: str, cache: str) -> int:
    """Fresh-process probe (``--aot-child``): time the first serve round."""
    from repro.uvm.manager import TenantMux
    from repro.uvm.server import SyncDispatch, StreamSession, drive
    from repro.uvm.server.aot import enable_aot

    lines, mcfg = make_workload(3, 192)
    trainer = Trainer(mcfg.predictor, mcfg.train, mcfg.kind)
    if mode != "jit":
        enable_aot(trainer, cache)
    session = StreamSession(TenantMux(mcfg, trainer=trainer), default_tenant="default")
    dispatch = SyncDispatch(trainer, mcfg.use_lucir)
    t0 = time.time()
    records = [r for ln in lines for r in drive(session.step(ln), dispatch)]
    records += drive(session.drain(), dispatch)
    out = {"mode": mode, "first_rounds_s": round(time.time() - t0, 3),
           "records": records}
    if mode != "jit":
        out["cache"] = trainer.aot_cache.stats()
    print(json.dumps(out))
    return 0


def bench_aot() -> dict:
    """Three fresh subprocesses: jit-cold, AOT export (populates the
    cache), AOT reload (trace+lower skipped).  Equality is part of the
    contract: the reloaded executables must reproduce the jit records."""
    out = {}
    with tempfile.TemporaryDirectory() as cache:
        for mode in ("jit", "export", "reload"):
            proc = subprocess.run(
                [sys.executable, "-m", "benchmarks.serve_perf",
                 "--aot-child", "export" if mode == "export" else
                 ("reload" if mode == "reload" else "jit"),
                 "--aot-cache", cache],
                capture_output=True, text=True, check=True)
            out[mode] = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["reload"]["cache"]["hits"] > 0, out["reload"]["cache"]
    assert out["reload"]["records"] == out["jit"]["records"], "AOT records != jit records"
    row = {
        "jit_cold_s": out["jit"]["first_rounds_s"],
        "aot_export_cold_s": out["export"]["first_rounds_s"],
        "aot_reload_cold_s": out["reload"]["first_rounds_s"],
        "reload_cache": out["reload"]["cache"],
        "records_equal": True,
        "derived": (f"reload {out['reload']['first_rounds_s']:.1f}s vs "
                    f"jit {out['jit']['first_rounds_s']:.1f}s cold"),
    }
    for m in out.values():
        m.pop("records", None)
    return row


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="CI scale (32 clients), assert the acceptance gates")
    ap.add_argument("--aot", action="store_true",
                    help="also run the AOT compile-once section (3 subprocesses)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the committed BENCH_serve.json")
    ap.add_argument("--aot-child", help=argparse.SUPPRESS)
    ap.add_argument("--aot-cache", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.aot_child:
        return _aot_child(args.aot_child, args.aot_cache)

    scale = "smoke" if args.smoke else "full"
    n_clients, n_batches, _, _ = SCALES[scale]
    t0 = time.time()
    # the AOT children need the device, so they run before this process uses it
    aot_row = bench_aot() if args.aot else None
    rows = bench_serve(scale)
    serial, batched = rows[0], rows[1]
    if args.smoke:
        # acceptance gates: N concurrent sessions served cleanly, the
        # dispatcher actually batched across connections, and the
        # microbatched mode measurably beats per-connection serial
        for r in rows:
            assert r["errors"] == 0, r
            assert r["actions"] == n_clients * n_batches, r
        assert batched["max_eval_lanes"] >= Trainer.MIN_VMAP_LANES, batched
        assert batched["speedup_x"] > 1.0, (serial, batched)
    # the AOT row has its own schema; pad to the key union for one CSV
    all_rows = rows + ([aot_row] if aot_row else [])
    keys = list(dict.fromkeys(k for r in all_rows for k in r))
    emit("serve_perf", [{k: r.get(k, "") for k in keys} for r in all_rows], t0)

    if BASELINE_PATH.exists():
        base = json.loads(BASELINE_PATH.read_text())
        prev = base.get(scale, {}).get("speedup_x")
        if prev:
            print(f"# committed {scale} speedup {prev}x; this run {batched['speedup_x']}x")
    else:
        base = {}
    if args.update_baseline:
        base[scale] = {
            "clients": n_clients,
            "engine": batched["engine"],
            "speedup_x": batched["speedup_x"],
            "serial": {k: serial[k] for k in ("wall_s", "faults_per_s", "p50_ms", "p99_ms")},
            "batched": {k: batched[k] for k in
                        ("wall_s", "faults_per_s", "p50_ms", "p99_ms", "ticks", "max_eval_lanes")},
        }
        if aot_row is not None:
            base["aot"] = {k: v for k, v in aot_row.items() if k != "derived"}
        BASELINE_PATH.write_text(json.dumps(base, indent=2) + "\n")
        print(f"# recorded {scale} section into {BASELINE_PATH}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
