"""Make the Section V-A pretrained model table that the learned cells load.

The table is the program's own ``runtime.pretrain_table`` on the corpus of
the configuration's ``pretrain`` block (the recipe ``Session.paper()``
uses: ATAX, Backprop, BICG, Hotspot and NW at scale 0.6, generator seeds
777..781, two rounds), with the configuration's predictor and training
settings.  It is written once, committed, and loaded at set-up, so no run
pretrains.  Each slot keeps its parameters, its Adam moments and its
counters; the LUCIR snapshot (``prev_params``) is left out, because the
manager overwrites it before its first use.

    PYTHONPATH=src python -m bench.make_table gpgpu-suite
"""
from __future__ import annotations

import argparse
import json
import os
import sys

from bench import tables
from bench.registry import load_config


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("config", help="configuration name under bench/configs")
    args = ap.parse_args(argv)
    os.environ["REPRO_PRETRAIN_CACHE"] = "0"  # write nothing into the program's memo directory
    cfg = load_config(args.config)
    tables.ensure_src_on_path()
    from repro.uvm import runtime as R
    from repro.uvm import trace as T

    pre = cfg["pretrain"]
    corpus = [T.BENCHMARKS[n](scale=pre["scale"], seed=pre["seed0"] + i)
              for i, n in enumerate(pre["benchmarks"])]
    pcfg, tcfg = tables.predictor_config(cfg), tables.train_config(cfg)
    table = R.pretrain_table(corpus, pcfg, tcfg, kind="transformer", max_rounds=pre["max_rounds"])
    path = tables.table_path(cfg)
    tables.save_table(table, path)
    print(json.dumps({"table": str(path), "slots": sorted(table.slots)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
