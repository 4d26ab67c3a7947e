"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick (reduced traces)
    PYTHONPATH=src python -m benchmarks.run --scale paper
    PYTHONPATH=src python -m benchmarks.run --only table6 fig14

Output: `name,us_per_call,derived` CSV lines + experiments/bench/<name>.csv.
"""
from __future__ import annotations

import argparse
import time

from benchmarks import figures, tables
from repro.uvm.api import Session


SUITES = {
    "fig3": figures.fig3,
    "fig4": figures.fig4,
    "fig6": figures.fig6,
    "fig10": figures.fig10,
    "fig11": figures.fig11,
    "fig12": figures.fig12,
    "fig13": figures.fig13,
    "fig14": figures.fig14,
    "table1": tables.table1,
    "table2": tables.table2,
    "table3": tables.table3,
    "table4": tables.table4,
    "table6": tables.table6,
    "table7": tables.table7,
    "table8": tables.table8,
    "table9": tables.table9,
    "table10": tables.table10,
}

# cheap first, NN-heavy later (shared caches warm up in order)
ORDER = ["table1", "table2", "table3", "table4", "fig3", "fig4", "fig6", "fig10", "fig11", "fig12", "table6", "fig13", "fig14", "table7", "table8", "table9", "table10"]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scale", choices=["quick", "paper"], default="quick")
    ap.add_argument("--only", nargs="*", default=None)
    args = ap.parse_args(argv)
    ctx = Session.paper() if args.scale == "paper" else Session()
    names = args.only or ORDER
    t0 = time.time()
    for name in names:
        SUITES[name](ctx)
    print(f"# total {time.time() - t0:.0f}s, results in experiments/bench/")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
