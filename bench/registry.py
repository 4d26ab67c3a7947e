"""Find a cell's pieces by name.

``BENCHMARK.json`` names each cell's configuration, traffic mix and
metrics; each lives in a file of its own, so a later change adds a file
and an entry and edits nothing that is there:

* configuration ``<name>``: the ``file`` its entry in ``configs`` gives;
* traffic mix ``<name>``: ``bench/traffic/<name>.json``;
* per-layer metric ``<name>``: ``bench/metrics/<name>.py``, a module with
  ``read(view) -> float | None`` (and ``PROGRAMS``, the jitted program
  names it keys on, where it reads device time).
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def cell(spec: dict, name: str) -> dict:
    for w in spec["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; one of {[w['name'] for w in spec['workloads']]}")


def load_config(name: str, spec: dict | None = None, root: Path = ROOT) -> dict:
    spec = spec if spec is not None else load_benchmark(root)
    for c in spec["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "traffic" / f"{name}.json").read_text())


def load_metric(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def end_to_end(spec: dict, workload: str) -> list[dict]:
    """The end-to-end metrics this cell reports."""
    return [m for m in spec["end_to_end"] if workload in m.get("workloads", [workload])]


def per_layer(spec: dict, workload: str) -> list[dict]:
    """The per-layer metrics this cell reports: those that list it, and
    those without a list whose ``moves`` metric the cell reports."""
    moved = {m["name"] for m in end_to_end(spec, workload)}
    return [m for m in spec["per_layer"]
            if (workload in m["workloads"] if "workloads" in m else m["moves"] in moved)]
