"""Run one cell of the benchmark and print its result as the last line.

    python3 -m bench.run --workload ours.suite-125 --seed 7 --seconds 30 --trace 0

Set-up (start-up, inputs from ``--seed``, the pretrained table, one warm
pass over every shape the window uses) is ``setup_s``; then the window
runs for ``--seconds``; then the window's answers are compared with the
plain references (``bench/check.py``).  ``--trace 1`` traces a window of
the traffic mix's ``trace_seconds`` instead (short enough for the
profiler to record whole; traced again, up to ``TRACE_TRIES`` windows,
where the profiler lost device programs) and reports the cell's
per-layer metrics instead of its end-to-end ones.  Exits 2 without the repository around it, 1 without a
TPU or with fewer chips than the cell asks for.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ROOT / ".bench_trace"
CACHE_DIR = ROOT / ".bench_cache"  # JAX's persistent compilation cache, at a fixed path
TRACE_TRIES = 3  # traced windows a run makes while the profiler loses device programs


def process_age_s() -> float:
    """Seconds since this process started (Linux), else since import."""
    try:
        ticks = os.sysconf("SC_CLK_TCK")
        start = int(Path("/proc/self/stat").read_text().rsplit(")", 1)[1].split()[19]) / ticks
        return float(Path("/proc/uptime").read_text().split()[0]) - start
    except (OSError, ValueError, IndexError):
        return time.perf_counter() - _IMPORTED


_IMPORTED = time.perf_counter()


def _configure_jax():
    """JAX with its persistent compilation cache in the checkout, one
    fixed directory per backend, every program cached."""
    import jax

    cache = CACHE_DIR / jax.default_backend()
    cache.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(cache))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def trace_options(jax):
    """The profiler's options for a traced window: the benchmark's own
    host spans and the device's operations, nothing of Python."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def _peak_bytes(devices) -> int | None:
    peaks = []
    for d in devices:
        try:
            stats = d.memory_stats() or {}
        except (RuntimeError, NotImplementedError):
            stats = {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


class _HostProbe:
    """What the host did during the window, to tell a stall's cause:
    this process's context switches and page faults, the machine's
    pressure-stall totals (Linux PSI, microseconds), and the garbage
    collector's pauses."""

    PSI = ("cpu", "memory", "io")

    def __init__(self):
        self.gc_pauses: list = []  # (generation, seconds) per collection
        self._gc_t0 = None

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((info.get("generation"), time.perf_counter() - self._gc_t0))
            self._gc_t0 = None

    @classmethod
    def _read(cls) -> dict:
        import resource

        ru = resource.getrusage(resource.RUSAGE_SELF)
        out = {"utime_s": ru.ru_utime, "stime_s": ru.ru_stime, "majflt": ru.ru_majflt, "minflt": ru.ru_minflt,
               "nvcsw": ru.ru_nvcsw, "nivcsw": ru.ru_nivcsw}
        for kind in cls.PSI:
            try:
                for line in Path(f"/proc/pressure/{kind}").read_text().splitlines():
                    f = dict(p.split("=") for p in line.split()[1:])
                    out[f"psi_{kind}_{line.split()[0]}_us"] = int(f["total"])
            except (OSError, ValueError, KeyError):
                pass
        return out

    def __enter__(self):
        self._start = self._read()
        gc.callbacks.append(self._gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._gc)
        end = self._read()
        self.delta = {k: end[k] - self._start[k] for k in end if k in self._start}
        gen2 = [s for g, s in self.gc_pauses if g == 2]
        self.delta.update(gc_n=len(self.gc_pauses), gc_s=sum(s for _, s in self.gc_pauses),
                          gc_max_s=max((s for _, s in self.gc_pauses), default=0.0), gc2_n=len(gen2))
        return False


class _CompileCounter:
    """Counts the backend compiles that happen while it is armed."""

    def __init__(self, jax):
        self.n, self.armed = 0, False

        def listen(event, duration, **kw):
            if self.armed and event == "/jax/core/compile/backend_compile_duration":
                self.n += 1

        jax.monitoring.register_event_duration_secs_listener(listen)


def _read_trace(trace_dir: Path, counters: dict, cfg: dict, devices, synced):
    """The traced window as :mod:`bench.reduce` sees it."""
    from bench import reduce
    from bench.peaks import peaks

    raw = reduce.load(sorted(trace_dir.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)[-1])
    if "eval_samples" in counters:
        counters = dict(counters, predictor=cfg["predictor"], batch_size=cfg["train"]["batch_size"])
    kind_peaks = peaks(devices[0].device_kind) if devices[0].platform == "tpu" else {}
    return reduce.View(raw["modules"], raw["spans"], counters, kind_peaks, len(devices), synced)


def run_cell(spec: dict, name: str, seed: int, seconds: float, trace: bool, *,
             cfg: dict | None = None, traffic: dict | None = None, t0_age: float = 0.0,
             control: bool = False) -> dict:
    """Set up, measure and check one cell on whatever devices JAX has.
    ``cfg``/``traffic`` replace the named files (tests run small ones);
    ``control=True`` scores the control in the program's place."""
    from bench import check, registry
    from bench.drivers import DRIVERS
    from bench.spans import Spans

    t_setup = time.perf_counter()
    jax = _configure_jax()
    cell = registry.cell(spec, name)
    cfg = cfg if cfg is not None else registry.load_config(cell["config"], spec)
    if cfg.get("matmul_precision") == "highest":  # float32 as the configuration states it
        jax.config.update("jax_default_matmul_precision", "highest")
    traffic = traffic if traffic is not None else registry.load_traffic(cell["traffic"])
    devices = jax.devices()[: cell["chips"]]
    compiles = _CompileCounter(jax)
    spans = Spans(trace)
    drv = DRIVERS[traffic["driver"]](cfg, traffic, seed, spans)
    drv.setup()
    setup_s = t0_age + time.perf_counter() - t_setup

    trace_dir = TRACE_DIR / name
    for attempt in range(1, TRACE_TRIES + 1 if trace else 2):
        before = dict(drv.counters)
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            jax.profiler.start_trace(str(trace_dir), profiler_options=trace_options(jax))
        compiles.armed = True
        try:
            with spans("window"), _HostProbe() as host:
                stats = drv.window(seconds, traced=trace)
        finally:
            compiles.armed = False
            if trace:
                jax.profiler.stop_trace()
        counters = {k: v - before[k] for k, v in drv.counters.items()}  # this window's
        if trace:
            view = _read_trace(trace_dir, counters, cfg, devices, drv.SYNCED)
            if view.complete or not view.modules:
                break
            print(f"bench.run: traced window {attempt} lost device programs ({view.uncovered()} calls hold "
                  "none)" + ("; tracing again" if attempt < TRACE_TRIES else "; its device metrics are left out"),
                  file=sys.stderr)
    memory_peak = _peak_bytes(devices)
    drv.release()
    gc.collect()

    limits = cfg["limits"]
    result_checks, failed = check.run(drv, limits, control=control)
    numbers = result_checks["numbers"]
    correct = all(numbers[k] <= limits[k] for k in numbers)
    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind, "count": len(jax.devices()),
              "memory_peak_bytes": memory_peak}
    out = {"correct": bool(correct), "attempted": counters.get("rounds") or counters.get("calls", 0),
           "failed": int(failed)}
    values = dict(stats, setup_s=setup_s)
    extra = {"window_s": stats["window_s"], "compiles_in_window": compiles.n, "host": host.delta, "counters": counters,
             "check_info": result_checks["info"], "seed": seed}
    if hasattr(drv, "workload_s"):
        extra.update(workload_s=drv.workload_s, record_s=drv.record_s)
    if not trace:
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in registry.end_to_end(spec, name)}
    else:
        extra.update(trace_uncovered=view.uncovered(), trace_attempts=attempt)
        metrics = {}
        for m in registry.per_layer(spec, name):
            value = registry.load_metric(m["name"]).read(view)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device.update(busy_s=view.busy_s(), window_s=view.window_s)
        out["breakdown"] = {"device_ops": view.top_programs(), "idle_gaps": view.idle_gaps()}
        extra["idle_gaps_at"] = view.idle_gaps(5, with_start=True)
    out.update(metrics=metrics, device=device, extra=extra)
    out["checks"] = {k: {"value": v, "limit": limits[k]} for k, v in numbers.items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir() or not (ROOT / "BENCHMARK.json").is_file():
        print(f"bench.run: no repository around {ROOT}", file=sys.stderr)
        return 2
    from bench import registry

    spec = registry.load_benchmark()
    try:
        cell = registry.cell(spec, args.workload)
    except KeyError as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    jax = _configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench.run: {args.workload} needs {cell['chips']} TPU chip(s); JAX has "
              f"{len(devices)} {devices[0].platform} device(s)", file=sys.stderr)
        return 1
    out = run_cell(spec, args.workload, args.seed, args.seconds, bool(args.trace), t0_age=process_age_s())
    for k, c in out["checks"].items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
