"""What decides ``correct``: the timed path's answers against the plain
references of :mod:`bench.reference`.

Numbers compared (each against the limit in the configuration file):

* ``sim_mismatch`` — simulator answers that differ from the reference
  simulator: per-access fault, thrash and evicted-before flags of every
  round of every workload's first run in the window, replayed from the
  manager's staged actions (learned cells), or every lane's counters of
  a seeded sample of workloads (sweep cells); plus every later run of a
  workload that does not repeat its first run's answers.  Exact: limit 0.
* ``table_mismatch`` — entries of the prediction-frequency table's dense
  exports (what the simulator's learned policy reads) that differ from a
  block-at-a-time table fed the same predictions.  Exact: limit 0.
* ``pred_gap`` — over a seeded sample of the window's ``evaluate`` calls,
  the widest gap by which the logit of the class the program predicted
  lies below the reference's best logit (float32, ``highest`` matmuls,
  the same parameters and features).
* ``train_gap_ratio`` — over a seeded sample of the window's fine-tunes,
  the worst one's gap between the program's parameter change and the
  reference's, over the reference's own gap when it sums each batch in
  another order (see :func:`train_readings`).

``control=True`` puts the control in the program's place.  In a learned
cell that is the predictor reference one precision below the
configuration's (the simulator and the table have no precision: the
reference in their place reads 0, so they are left out).  In a sweep
cell, which runs no model, it is the reference simulator on a device one
block larger than the configuration states: a broken capacity guarantee.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from bench import reference as ref
from bench.drivers import bucket_blocks, capacity_for

#: float32's unit roundoff
F32_UNIT = 2.0 ** -24


def control_prec(cfg: dict) -> str:
    """The precision just below the one the configuration states: float32
    at ``high`` (three bfloat16 passes) below float32 at ``highest``,
    bfloat16 below any other float32."""
    return "high" if cfg.get("matmul_precision") == "highest" else "bfloat16"


def _feats(fs) -> dict:
    return {"page": fs.page, "delta": fs.delta, "pc": fs.pc, "tb": fs.tb}


def _pad_rows(a: np.ndarray, n: int) -> np.ndarray:
    return np.concatenate([a, np.zeros((n - len(a),) + a.shape[1:], a.dtype)]) if len(a) < n else a


def _rows_bucket(n: int) -> int:
    return max(1 << (n - 1).bit_length(), 256)


def pred_gap(drv, control: bool) -> float:
    """The widest gap by which the predicted class's logit lies below the
    reference's best."""
    import jax.numpy as jnp

    p = drv.cfg["predictor"]
    kw = dict(n_layers=p["num_layers"], cosine_scale=float(p["cosine_scale"]))
    gap = 0.0
    for e in drv.evals:
        n = len(e["fs"])
        rows = _rows_bucket(n)
        batch = {k: jnp.asarray(_pad_rows(np.asarray(a, np.int32), rows)) for k, a in _feats(e["fs"]).items()}
        want = np.asarray(ref.logits_of(e["params"], batch, e["n_active"], **kw))[:n]
        if control:
            pred = np.asarray(ref.logits_of(e["params"], batch, e["n_active"], prec=control_prec(drv.cfg), **kw))[:n]
            pred = pred.argmax(-1)
        else:
            pred = np.asarray(e["pred"])
        gap = max(gap, float(np.max(want.max(-1) - want[np.arange(n), pred])))
    return gap


def _change_gap(p0: dict, want: dict, got: dict, keep: list) -> float:
    """The worst leaf's gap between the norms of two parameter changes
    from ``p0``, over the larger of that leaf's change in ``want`` and the
    median leaf's."""
    moved = {k: float(np.linalg.norm(np.asarray(want[k], np.float64) - p0[k])) for k in keep}
    med = float(np.median(list(moved.values())))
    return max(abs(float(np.linalg.norm(np.asarray(got[k], np.float64) - p0[k])) - moved[k]) / max(moved[k], med)
               for k in keep)


def train_readings(drv, control: bool) -> dict:
    """Per sampled fine-tune, the reference runs the same AdamW steps from
    the same start, features, labels, thrash flags and schedule twice:
    as drawn, and with each batch's rows in reverse order (the same sums,
    rounded in another order).  ``gap``: :func:`_change_gap` of the
    program's parameters (the control's, with ``control``) against the
    reference's; ``spread``: the same of the reversed reference.  A
    fine-tune that amplifies rounding over its steps does so in both, so
    ``train_gap_ratio``, the worst fine-tune's gap over its spread, reads
    the program's rounding against the reference's own.  One reordering
    can miss part of a fine-tune's sensitivity, so a spread counts as no
    less than float32's unit once per step of a whole group's fine-tune,
    what rounding alone can add up to over it.  Leaves whose first
    reference gradient is under a thousandth of the median leaf's are
    left out (they move by round-off alone).  ``train_gaps``: each
    fine-tune's [gap, spread]."""
    import jax

    pcfg, tcfg = drv.cfg["predictor"], drv.cfg["train"]
    pairs = []
    for t in drv.trains:
        feats, labels = _feats(t["fs"]), np.asarray(t["fs"].label)
        args = (t["params"], t["m"], t["v"], t["step"], t["prev"], feats, labels, t["in_et"], t["n_active"])
        kw = dict(pcfg=pcfg, tcfg=tcfg, use_lucir=t["use_lucir"])
        if "refs" not in t:  # the program's and the control's readings share them
            want = ref.train_group(*args, **kw)
            other = ref.train_group(*args, reverse_rows=True, **kw)
            grads = ref.first_grad_norms(*args, **kw)
            p0, want, other = jax.device_get((t["params"], want, other))
            p0 = {k: np.asarray(a, np.float64) for k, a in p0.items()}
            med_g = float(np.median(list(grads.values())))
            t["refs"] = (p0, want, other, [k for k in p0 if grads[k] >= 1e-3 * med_g])
        p0, want, other, keep = t["refs"]
        got = jax.device_get(ref.train_group(*args, prec=control_prec(drv.cfg), **kw) if control else t["new"])
        pairs.append([_change_gap(p0, want, got, keep), _change_gap(p0, want, other, keep)])
    floor = F32_UNIT * (tcfg["epochs"] * tcfg["group_size"] // tcfg["batch_size"])
    ratio = max((g / max(s, floor) for g, s in pairs), default=0.0)
    return {"train_gap_ratio": ratio, "train_gaps": pairs}


def learned_sim(drv) -> tuple[int, int]:
    """Simulator mismatches, and the workload runs with any mismatch."""
    G = drv.tcfg.group_size
    bad, failed = 0, 0
    for w, rec in sorted(drv.first.items()):
        tr = drv.traces[w]
        cap = capacity_for(tr.n_blocks, drv.cfg["oversubscription"])
        sim = ref.RefSim(bucket_blocks(tr.n_blocks), tr.n_blocks, tr.block)
        rounds = [(i * G, min((i + 1) * G, len(tr)), a.counters, a.prefetch_blocks)
                  for i, a in enumerate(rec["actions"])]
        outs, stats = sim.learned(rounds, cap, pad_to=G)
        n = abs(len(outs) - len(rec["outs"])) * G
        for o, p in zip(outs, rec["outs"]):
            n += sum(int(np.count_nonzero(np.asarray(o[k]) != np.asarray(p[k])))
                     for k in ("fault", "thrash", "was_evicted"))
        n += sum(int(stats[k] != rec["stats"].get(k)) for k in stats)
        bad += n
        failed += n > 0
    return bad, failed


def table_mismatch(drv) -> tuple[int, int]:
    """Mismatching entries, and the dense exports compared."""
    bad, exports = 0, 0
    for log in drv.tables_log.values():
        t = ref.LoopTable()
        for op in log:
            if op[0] == "update":
                t.update(op[1])
            elif op[0] == "flush":
                t.intervals(op[1])
            else:
                bad += int(np.count_nonzero(t.dense(op[1]) != np.asarray(op[2])))
                exports += 1
    return bad, exports


def _digest(answers) -> str:
    """A digest of a run's answers: arrays, scalars, and the manager's
    staged actions (their counters and prefetch blocks), nested in dicts,
    lists and tuples."""
    h = hashlib.sha1()

    def feed(x):
        if isinstance(x, dict):
            for k in sorted(x):
                h.update(str(k).encode())
                feed(x[k])
        elif isinstance(x, (list, tuple)):
            h.update(b"[")
            for v in x:
                feed(v)
            h.update(b"]")
        elif x is None:
            h.update(b"|")
        elif hasattr(x, "prefetch_blocks"):
            feed((x.counters, x.prefetch_blocks))
        else:
            a = np.asarray(x)
            h.update(str(a.dtype).encode() + np.ascontiguousarray(a).tobytes())

    feed(answers)
    return h.hexdigest()


def repeats(drv) -> tuple[int, int]:
    """Runs in the window whose answers differ from the first run of the
    same workload (the program is deterministic), digested now that the
    window has closed."""
    first = {}
    bad = 0
    for w, answers in drv.runs:
        d = _digest(answers)
        first.setdefault(w, d)
        bad += d != first[w]
    return bad, bad


def sweep_sim(drv, control: bool) -> tuple[int, int]:
    bad, failed = 0, 0
    for w in drv.check_sample:
        if w not in drv.first:
            continue
        tr = drv.traces[w]
        cells = [(ref.POLICIES.index(p), ref.PREFETCHERS.index(f),
                  capacity_for(tr.n_blocks, o) + (1 if control else 0)) for p, f, o in drv.lanes]
        want = ref.RefSim(bucket_blocks(tr.n_blocks), tr.n_blocks, tr.block).sweep(cells)
        n = sum(int(a.get(k) != b[k]) for a, b in zip(drv.first[w], want) for k in b)
        bad += n
        failed += n > 0
    return bad, failed


def run(drv, limits: dict, control: bool = False) -> tuple[dict, int]:
    """The numbers that ``limits`` names, the others read beside them
    (``info``), and the answers found wrong."""
    t0 = time.perf_counter()
    if hasattr(drv, "evals"):
        read, failed, info = {}, 0, {"eval_calls": len(drv.evals), "train_calls": len(drv.trains),
                                     "checked_runs": len(drv.first), "seconds": {}}
        if not control:
            rep, rep_failed = repeats(drv)
            sim, failed = learned_sim(drv)
            failed += rep_failed
            t1 = time.perf_counter()
            table_bad, info["table_exports"] = table_mismatch(drv)
            read.update(sim_mismatch=sim + rep, table_mismatch=table_bad)
            info["seconds"].update(sim=t1 - t0, table=time.perf_counter() - t1)
        t2 = time.perf_counter()
        read["pred_gap"] = pred_gap(drv, control)
        t3 = time.perf_counter()
        read.update(train_readings(drv, control))
        info["seconds"].update(pred=t3 - t2, train=time.perf_counter() - t3)
    else:
        rep, rep_failed = repeats(drv)
        sim, failed = sweep_sim(drv, control)
        failed += rep_failed
        read = {"sim_mismatch": sim + rep}
        info = {"checked_workloads": [w for w in drv.check_sample if w in drv.first], "lanes_sharded": drv.sharded,
                "seconds": {"sim": time.perf_counter() - t0}}
    numbers = {k: read[k] for k in limits if k in read}
    info["readings"] = {k: v for k, v in read.items() if k not in numbers}
    return {"numbers": numbers, "info": info}, failed
