"""Small versions of the benchmark's cells for the CPU tests: the real
configuration and traffic files, with fewer and shorter traces."""
from __future__ import annotations

import contextlib
import copy
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import registry, tables  # noqa: E402

SPEC = registry.load_benchmark()

#: the smoke predictor's widths, for the tests that only need a run
SMOKE = dict(d_model=16, d_ff=32, num_layers=1, page_vocab=64, delta_vocab=32, pc_vocab=16, tb_vocab=16)


def small(name: str, *, paper_predictor: bool = True, scale: float = 0.05, workloads: int = 3):
    """(config, traffic) of cell ``name`` cut to a CPU test's size.
    ``paper_predictor=False`` also swaps in the smoke predictor with
    256-access groups (run it under :func:`fresh_table`)."""
    cell = registry.cell(SPEC, name)
    cfg = copy.deepcopy(registry.load_config(cell["config"], SPEC))
    traffic = copy.deepcopy(registry.load_traffic(cell["traffic"]))
    cfg["scale"] = scale
    cfg["workloads"] = cfg["workloads"][:workloads]
    if not paper_predictor:
        cfg["predictor"].update(SMOKE)
        cfg["train"].update(group_size=256, batch_size=64)
    return cfg, traffic


@contextlib.contextmanager
def fresh_table():
    """The pretrained table's file holds the paper predictor's weights;
    under this, a run of the smoke predictor starts every pattern from
    fresh weights instead."""
    from repro.core.model_table import ModelTable

    def fresh(path, trainer):
        return ModelTable(lambda s: trainer.new_params(s), n_slots=trainer.tcfg.table_slots)

    with mock.patch.object(tables, "load_table", fresh):
        yield


def run(name: str, seed: int = 2**31 + 11, seconds: float = 1.0, trace: bool = False, control=False,
        paper_predictor: bool = True, **kw):
    from bench.run import run_cell

    cfg, traffic = small(name, paper_predictor=paper_predictor, **kw)
    with contextlib.nullcontext() if paper_predictor else fresh_table():
        return run_cell(SPEC, name, seed, seconds, trace, cfg=cfg, traffic=traffic, control=control)
