"""Read a cell's compared numbers for the program, for the control and
for planted faults, seed after seed, in one process: the readings that
the limits in the configuration files are set from.

For each seed the cell's driver builds its inputs, runs one whole pass
(every workload once: the window's own calls at the cell's size), and
every number of ``bench/check.py`` is read twice: with the program's
answers, and with the control in the program's place (learned cells:
the predictor reference one precision below the configuration's, float32
at ``high`` below float32 at ``highest``; sweep cells: the reference
simulator on a device one block larger than the configuration states).
On the first ``--fault-seeds`` seeds each fault of ``bench/faults.py``
is planted in turn, one more pass each.  One JSON line per seed.  The
benchmark's own runs never run this.

    python3 -m bench.control --workload ours.suite-125 --seeds 11 12 13 --fault-seeds 3 [--faults half_batch]
"""
from __future__ import annotations

import argparse
import json
import sys

CANDIDATES = ("sim_mismatch", "table_mismatch", "pred_gap", "train_gap_ratio", "train_gaps")


def readings(workload: str, seeds: list[int], fault_seeds: int = 0, *, cfg: dict | None = None,
             traffic: dict | None = None, only: list[str] | None = None):
    """Yield ``{"seed", "program", "control", "faults"}`` per seed."""
    from bench import check, faults, registry
    from bench.drivers import DRIVERS
    from bench.run import _configure_jax
    from bench.spans import Spans

    jax = _configure_jax()
    spec = registry.load_benchmark()
    cell = registry.cell(spec, workload)
    cfg = cfg if cfg is not None else registry.load_config(cell["config"], spec)
    traffic = traffic if traffic is not None else registry.load_traffic(cell["traffic"])
    if cfg.get("matmul_precision") == "highest":
        jax.config.update("jax_default_matmul_precision", "highest")
    names = dict.fromkeys(CANDIDATES, 0)
    planted = faults.LEARNED if traffic["driver"] == "learned" else faults.SWEEP

    def one_pass(seed):
        drv = DRIVERS[traffic["driver"]](cfg, traffic, seed, Spans(False))
        drv.setup(warm=False)
        drv.window(0.0)
        drv.release()
        return drv

    for i, seed in enumerate(seeds):
        drv = one_pass(seed)
        out = {"seed": seed}
        for side, control in (("program", False), ("control", True)):
            out[side] = check.run(drv, names, control=control)[0]["numbers"]
        if i < fault_seeds:
            out["faults"] = {}
            for name, fault in planted.items():
                if only and name not in only:
                    continue
                with fault():
                    out["faults"][name] = check.run(one_pass(seed), names)[0]["numbers"]
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__, formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, default=0)
    ap.add_argument("--faults", nargs="*", default=None, help="plant only these faults (default: every one)")
    args = ap.parse_args(argv)
    for line in readings(args.workload, args.seeds, args.fault_seeds, only=args.faults):
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
