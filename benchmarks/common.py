"""Shared harness for the per-table/figure benchmarks — now a thin layer
over :mod:`repro.uvm.api`.

The session/caching logic that used to live here moved into
:class:`repro.uvm.api.Session`, which additionally persists every computed
cell in the content-addressed run store under ``experiments/runs/`` —
rerunning a table after a crash (or after the CLI already swept the same
cells) recomputes nothing.  (The deprecated ``Ctx`` shim that bridged the
historical constructor signature completed its removal schedule and is
gone; construct a :class:`Session` directly.)

`--scale quick` (default) runs reduced traces on CPU in minutes;
`--scale paper` uses the full generator sizes.
"""
from __future__ import annotations

import csv
import time
from pathlib import Path

# importing the API configures the persistent XLA compile cache
# (repro.util.enable_compile_cache) before any jit runs
from repro.uvm.api import ALL_BENCH, FEATURED, Session  # noqa: F401

# Deprecated re-exports (PR 3 moved the configs to repro.configs.predictor_paper;
# in-tree call sites migrated in PR 10): accessing them warns DeprecationWarning,
# and the names are DELETED in the next PR — see docs/API.md for the schedule.
_DEPRECATED_CONFIGS = {"PCFG_QUICK": "CONFIG_QUICK", "PCFG_FULL": "CONFIG"}


def __getattr__(name: str):
    if name in _DEPRECATED_CONFIGS:
        import warnings

        from repro.configs import predictor_paper

        new = _DEPRECATED_CONFIGS[name]
        warnings.warn(
            f"benchmarks.common.{name} is deprecated and will be removed in the "
            f"next PR; import repro.configs.predictor_paper.{new} instead",
            DeprecationWarning,
            stacklevel=2,
        )
        return getattr(predictor_paper, new)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


OUT_DIR = Path("experiments/bench")


def emit(name: str, rows: list[dict], t0: float) -> None:
    """Write CSV + print the `name,us_per_call,derived` contract line."""
    OUT_DIR.mkdir(parents=True, exist_ok=True)
    path = OUT_DIR / f"{name}.csv"
    if rows:
        with path.open("w", newline="") as f:
            w = csv.DictWriter(f, fieldnames=list(rows[0].keys()))
            w.writeheader()
            w.writerows(rows)
    us = (time.time() - t0) * 1e6 / max(len(rows), 1)
    derived = rows[0].get("derived", "") if rows else ""
    print(f"{name},{us:.0f},{derived}")
    for r in rows:
        print("   ", {k: (round(v, 4) if isinstance(v, float) else v) for k, v in r.items()})
