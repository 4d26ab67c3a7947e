"""Training protocols for the page predictor (Sections III-C, IV-B, V-A/B).

  * online_single — ONE model, plain CE, train on group k-1 / predict group k
                    (the existing-learning-based-works protocol, Fig. 4).
  * online_multi  — pattern-aware model table, plain CE (Fig. 6 'multiple').
  * ours          — pattern-aware table + LUCIR distillation + (optionally)
                    the thrashing term (the full Section IV design).
  * offline       — train one model on a random 50% of samples (future info!)
                    then predict everything in temporal order: the paper's
                    upper bound (Figs. 4/11).

Every protocol measures top-1 accuracy on a group BEFORE the model trains on
it (strictly causal evaluation).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.configs.predictor_paper import PredictorConfig
from repro.core import losses
from repro.core.baselines_nn import make_model
from repro.core.features import DeltaVocab, FeatureSet, FeatureStream
from repro.core.model_table import Entry, ModelTable
from repro.core.pattern import PatternClassifier
from repro.distributed.compat import lane_shardings
from repro.optim import adamw
from repro.util import pow2_bucket as _pow2_rows
from repro.uvm.trace import Trace


def _shard_lane_trees(n_lanes: int, *trees):
    """Commit lane-stacked pytrees to a cross-device lanes sharding (lanes
    are independent models/groups, so GSPMD partitions the vmapped dispatch
    without communication).  No-op on a single device or when the lane
    count does not divide the devices; any device_put failure falls back to
    unsharded inputs."""
    lane_sh, _ = lane_shardings(n_lanes)
    if lane_sh is None:
        return trees
    try:
        return tuple(jax.tree.map(lambda x: jax.device_put(x, lane_sh), t) for t in trees)
    except Exception:
        return trees


@dataclasses.dataclass
class TrainConfig:
    group_size: int = 2048  # accesses per train/predict group (paper: 50M instr)
    epochs: int = 3
    batch_size: int = 256
    lr: float = 3e-3
    seed: int = 0
    table_slots: int = 8


def _batch_of(fs: FeatureSet, idx) -> dict:
    return {
        "page": jnp.asarray(fs.page[idx]),
        "delta": jnp.asarray(fs.delta[idx]),
        "pc": jnp.asarray(fs.pc[idx]),
        "tb": jnp.asarray(fs.tb[idx]),
    }


def _build_trainer_fns(pcfg: PredictorConfig, kind: str, lr: float):
    init_fn, forward = make_model(pcfg, kind)
    opt = adamw.adamw(lr, weight_decay=0.01)

    def train_step(params, opt_state, batch, labels, n_active, step, f_old, in_et, use_lucir, use_thrash):
        def lf(p):
            logits, f = forward(p, batch)
            return losses.total_loss(
                logits, f, labels,
                n_active=n_active,
                f_old=f_old if use_lucir else None,
                in_et=in_et if use_thrash else None,
                lam=pcfg.lucir_lambda, mu=pcfg.thrash_mu,
            )

        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        updates, opt_state, _ = opt.update(grads, opt_state, params, step)
        params = adamw.apply_updates(params, updates)
        return params, opt_state, metrics

    def eval_step(params, batch, labels, n_active):
        logits, f = forward(params, batch)
        lm = jnp.where(jnp.arange(logits.shape[-1]) >= n_active, -1e30, logits)
        return (lm.argmax(-1) == labels), lm.argmax(-1), f

    # Whole-group drivers: the per-batch python loops used to pay one jit
    # dispatch + one blocking device->host sync PER BATCH (the dominant cost
    # of run_ours once compiles are shared). Scanning over a precomputed
    # batch-index matrix runs the IDENTICAL per-batch computation — same
    # shapes, same op sequence, host-identical index construction — in one
    # dispatch with one sync at the end.
    def eval_scan(params, feats, labels, pidx, n_active):
        def body(_, idx):
            batch = {k: v[idx] for k, v in feats.items()}
            c, p, _ = eval_step(params, batch, labels[idx], n_active)
            return None, (c, p)

        _, (cs, ps) = jax.lax.scan(body, None, pidx)
        return cs, ps

    def train_scan(params, opt_state, step0, feats, labels, et, prev_params, idx_mat, valid, n_active, use_lucir, use_thrash):
        # idx_mat is padded to a bucketed row count so one compiled scan
        # serves every group size; padded rows (valid=False) leave the carry
        # untouched — numerically a strict no-op.
        def body(carry, xs):
            idx, v = xs

            def do(c):
                params, opt_state, step = c
                batch = {k: x[idx] for k, x in feats.items()}
                if use_lucir:
                    f_old = forward(prev_params, batch)[1]
                else:
                    f_old = jnp.zeros((idx.shape[0], pcfg.d_model))
                if use_thrash:
                    bet = et[idx]
                else:
                    bet = jnp.zeros((idx.shape[0],), bool)
                p, o, _ = train_step(
                    params, opt_state, batch, labels[idx], n_active, step, f_old, bet,
                    use_lucir=use_lucir, use_thrash=use_thrash,
                )
                return (p, o, step + 1)

            return jax.lax.cond(v, do, lambda c: c, carry), None

        (params, opt_state, _), _ = jax.lax.scan(body, (params, opt_state, step0), (idx_mat, valid))
        return params, opt_state

    # Cross-benchmark lanes: the SAME per-group computation vmapped over a
    # leading lane axis (params, features, labels, schedules, n_active all
    # stacked). One dispatch serves every benchmark in the shape bucket.
    def eval_scan_many(params, feats, labels, pidx, n_active):
        return jax.vmap(eval_scan)(params, feats, labels, pidx, n_active)

    def train_scan_many(params, opt_state, step0, feats, labels, et, prev_params, idx_mat, valid, n_active, use_lucir, use_thrash):
        return jax.vmap(
            lambda p, o, s, f, l, e, pp, im, v, na: train_scan(p, o, s, f, l, e, pp, im, v, na, use_lucir, use_thrash)
        )(params, opt_state, step0, feats, labels, et, prev_params, idx_mat, valid, n_active)

    # n_active is a traced arg (class count grows); use_lucir/use_thrash static
    return (
        init_fn, forward, opt,
        jax.jit(train_step, static_argnames=("use_lucir", "use_thrash")),
        jax.jit(eval_step),
        jax.jit(eval_scan),
        jax.jit(train_scan, static_argnames=("use_lucir", "use_thrash")),
        jax.jit(eval_scan_many),
        jax.jit(train_scan_many, static_argnames=("use_lucir", "use_thrash")),
    )


# One jitted train/eval pair per (config, architecture, lr): Trainer used to
# rebuild (and so recompile) its jits per INSTANCE, which put ~6s of XLA
# compilation in front of every run_ours/run_protocol call — the dominant
# cost of the table6/fig11 sweeps. The closures are pure functions of the
# (hashable, frozen) PredictorConfig + kind + lr, so sharing them is exact.
_TRAINER_FN_CACHE: dict = {}


class Trainer:
    """Jitted train/eval for one predictor architecture."""

    def __init__(self, pcfg: PredictorConfig, tcfg: TrainConfig, kind: str = "transformer"):
        self.pcfg, self.tcfg, self.kind = pcfg, tcfg, kind
        cache_key = (pcfg, kind, tcfg.lr)
        if cache_key not in _TRAINER_FN_CACHE:
            _TRAINER_FN_CACHE[cache_key] = _build_trainer_fns(pcfg, kind, tcfg.lr)
        (self.init_fn, self.forward, self.opt, self._train_step, self._eval_step,
         self._eval_scan, self._train_scan,
         self._eval_scan_many, self._train_scan_many) = _TRAINER_FN_CACHE[cache_key]

    @staticmethod
    def _stage(fs: FeatureSet):
        """Stage the group's features on device, padded to a power-of-two
        sample count so every group shares one compiled scan (each distinct
        array length would otherwise re-trace + re-lower it — several
        seconds per variant even with a warm persistent cache). Batch
        indices only ever address the first len(fs) rows, so padding rows
        are unreachable and the gathered batches are unchanged."""
        n_pad = _pow2_rows(len(fs), 1024) - len(fs)

        def pad(a):
            a = np.asarray(a)
            if n_pad:
                a = np.concatenate([a, np.zeros((n_pad,) + a.shape[1:], a.dtype)])
            return jnp.asarray(a)

        return (
            {"page": pad(fs.page), "delta": pad(fs.delta), "pc": pad(fs.pc), "tb": pad(fs.tb)},
            pad(fs.label),
        )

    def new_params(self, seed: int = 0):
        return self.init_fn(jax.random.key(seed))

    def _eval_schedule(self, n: int) -> np.ndarray:
        """Padded batch-index matrix for one group (host-identical to the
        old per-batch loop's index construction)."""
        B = self.tcfg.batch_size
        rows = []
        for lo in range(0, n, B):
            idx = np.arange(lo, min(lo + B, n))
            pad = B - len(idx)
            rows.append(np.concatenate([idx, np.zeros(pad, int)]) if pad else idx)
        n_rows = len(rows)
        rows += [np.zeros(B, np.int64)] * (_pow2_rows(n_rows, 8) - n_rows)  # compile-bucket rows
        return np.stack(rows).astype(np.int32)

    @obs.spanned("trainer.evaluate")
    def evaluate(self, params, fs: FeatureSet, n_active: int):
        """Top-1 correctness per sample + predicted class ids (all batches in
        one scanned dispatch; only the final padded batch carries junk rows,
        which are sliced off exactly as the per-batch loop did)."""
        n = len(fs)
        if n == 0:
            return np.zeros(0, bool), np.zeros(0, np.int32)
        with obs.span("trainer.stage"):
            pidx = jnp.asarray(self._eval_schedule(n))
            feats, labels = self._stage(fs)
        with obs.span("trainer.dispatch"):
            cs, ps = self._eval_scan(params, feats, labels, pidx, n_active)
        out = obs.to_host((cs, ps), "trainer.evaluate")  # one sync for the whole group
        correct = out[0].reshape(-1)[:n].astype(bool)
        pred = out[1].reshape(-1)[:n].astype(np.int32)
        return correct, pred

    # Below this lane count, batched dispatch is not worth a fresh vmapped
    # trace: the serial jits are already compiled (and shared with every
    # serial caller).  At or above it, lane counts pad to powers of two so
    # every sweep round hits one of a handful of compiled shapes.
    MIN_VMAP_LANES = 4

    @staticmethod
    def _pad_lanes(lanes: list, b_pad: int) -> list:
        """Pad a lane group by replicating its first lane (outputs of the
        padding lanes are discarded; replication keeps every array shape
        and dtype identical without inventing degenerate inputs)."""
        return lanes + [lanes[0]] * (b_pad - len(lanes))

    @obs.spanned("trainer.evaluate")
    def evaluate_many(self, params_list: list, fs_list: list, n_active_list: list):
        """Batched :meth:`evaluate` across lanes (one model + feature group
        per lane — the cross-benchmark case).  Lanes are grouped by their
        padded (sample, schedule) shapes; each group runs as one vmapped
        scan over stacked params.  Returns one (correct, pred) per lane."""
        results: list = [None] * len(fs_list)
        groups: dict = {}
        for i, fs in enumerate(fs_list):
            n = len(fs)
            if n == 0:
                results[i] = (np.zeros(0, bool), np.zeros(0, np.int32))
                continue
            pidx = self._eval_schedule(n)  # host-cheap; shapes decide the bucket
            groups.setdefault((_pow2_rows(n, 1024), pidx.shape[0]), []).append((i, pidx))
        for lanes in groups.values():
            if len(lanes) < self.MIN_VMAP_LANES:
                for i, _ in lanes:
                    results[i] = self.evaluate(params_list[i], fs_list[i], n_active_list[i])
                continue
            idxs = [i for i, _ in lanes]
            with obs.span("trainer.stage"):
                # device staging only happens once the bucket is known to vmap
                staged = [(i, *self._stage(fs_list[i]), p) for i, p in lanes]
                staged = self._pad_lanes(staged, _pow2_rows(len(staged), self.MIN_VMAP_LANES))
                pidxs = [i for i, *_ in staged]
                params = jax.tree.map(lambda *xs: jnp.stack(xs), *[params_list[i] for i in pidxs])
                feats = {k: jnp.stack([f[k] for _, f, _, _ in staged]) for k in staged[0][1]}
                labels = jnp.stack([l for _, _, l, _ in staged])
                pidx = jnp.asarray(np.stack([p for _, _, _, p in staged]))
                na = jnp.asarray(np.array([n_active_list[i] for i in pidxs], np.int32))
                lanes = staged
                params, feats, labels, pidx, na = _shard_lane_trees(len(lanes), params, feats, labels, pidx, na)
            with obs.span("trainer.dispatch"):
                cs, ps = self._eval_scan_many(params, feats, labels, pidx, na)
            out = obs.to_host((cs, ps), "trainer.evaluate_many")  # one sync per shape bucket
            for j, i in enumerate(idxs):
                n = len(fs_list[i])
                results[i] = (
                    out[0][j].reshape(-1)[:n].astype(bool),
                    out[1][j].reshape(-1)[:n].astype(np.int32),
                )
        return results

    def old_features(self, prev_params, fs: FeatureSet, idx):
        if prev_params is None:
            return None
        _, _, f = self._eval_step(prev_params, _batch_of(fs, idx), jnp.asarray(fs.label[idx]), 1)
        return f

    def _train_schedule(self, n: int, rng):
        """Padded batch-index schedule for one group (per-epoch permutation,
        full batches, tiny-group resize fallback) — host-identical rng call
        sequence to the original per-batch loop."""
        tc = self.tcfg
        rows = []
        for _ in range(tc.epochs):
            order = rng.permutation(n)
            for lo in range(0, n - tc.batch_size + 1, tc.batch_size):
                rows.append(order[lo : lo + tc.batch_size])
            if n < tc.batch_size:  # tiny group: single padded batch
                rows.append(np.resize(order, tc.batch_size))
        n_steps = len(rows)
        n_pad = _pow2_rows(n_steps, 16) - n_steps  # one compiled scan per step-count bucket
        rows += [np.zeros(tc.batch_size, np.int64)] * n_pad
        valid = np.arange(len(rows)) < n_steps
        return np.stack(rows).astype(np.int32), valid, n_steps

    def _stage_et(self, in_et, n: int):
        if in_et is None:
            return jnp.zeros(1, bool)
        et_np = np.asarray(in_et, bool)  # pad to the features' sample bucket
        return jnp.asarray(np.concatenate([et_np, np.zeros(_pow2_rows(n, 1024) - n, bool)]))

    @obs.spanned("trainer.train_group")
    def train_group(self, entry: Entry, fs: FeatureSet, n_active: int, *, in_et=None, use_lucir=False, rng=None):
        """Fine-tune on one group (a few epochs) in ONE scanned dispatch."""
        tc = self.tcfg
        if entry.opt_state is None:
            entry.opt_state = self.opt.init(entry.params)
        n = len(fs)
        if n == 0:
            return entry
        rng = np.random.default_rng(tc.seed if rng is None else rng)
        use_l = use_lucir and entry.prev_params is not None
        with obs.span("trainer.stage"):
            idx_mat, valid, n_steps = self._train_schedule(n, rng)
            feats, labels = self._stage(fs)
            et = self._stage_et(in_et, n)
            step0, na = jnp.asarray(entry.step, jnp.int32), jnp.asarray(n_active, jnp.int32)
            idx_mat, valid = jnp.asarray(idx_mat), jnp.asarray(valid)
        prev = entry.prev_params if use_l else entry.params  # ignored unless use_lucir
        with obs.span("trainer.dispatch"):
            entry.params, entry.opt_state = self._train_scan(
                entry.params, entry.opt_state, step0, feats, labels, et, prev, idx_mat, valid, na,
                use_lucir=use_l, use_thrash=in_et is not None,
            )
        entry.step += n_steps
        entry.n_updates += 1
        return entry

    @obs.spanned("trainer.train_group")
    def train_group_many(self, entries: list, fs_list: list, n_active_list: list, *, in_et_list=None, use_lucir=False):
        """Batched :meth:`train_group` across lanes (one entry + group per
        lane).  Lanes are grouped by (sample bucket, step bucket, LUCIR
        eligibility, thrash-term presence) — the static jit flags and array
        shapes that must agree inside one vmapped dispatch.  Entries are
        updated in place, exactly as the serial path does."""
        tc = self.tcfg
        in_et_list = in_et_list if in_et_list is not None else [None] * len(entries)
        groups: dict = {}
        for i, (entry, fs) in enumerate(zip(entries, fs_list)):
            n = len(fs)
            if n == 0:
                continue
            if entry.opt_state is None:
                entry.opt_state = self.opt.init(entry.params)
            use_l = use_lucir and entry.prev_params is not None
            # the schedule is host-cheap and its shape decides the bucket;
            # device staging waits until the bucket is known to vmap
            idx_mat, valid, n_steps = self._train_schedule(n, np.random.default_rng(tc.seed))
            key = (_pow2_rows(n, 1024), idx_mat.shape[0], use_l, in_et_list[i] is not None)
            groups.setdefault(key, []).append((i, idx_mat, valid, n_steps))
        for (_, _, use_l, use_thrash), lanes in groups.items():
            if len(lanes) < self.MIN_VMAP_LANES:
                for i, *_ in lanes:
                    self.train_group(
                        entries[i], fs_list[i], n_active_list[i],
                        in_et=in_et_list[i], use_lucir=use_lucir,
                    )
                continue
            idxs = [i for i, *_ in lanes]
            with obs.span("trainer.stage"):
                lanes = [
                    (i, *self._stage(fs_list[i]), self._stage_et(in_et_list[i], len(fs_list[i])), m, v, s)
                    for i, m, v, s in lanes
                ]
                lanes = self._pad_lanes(lanes, _pow2_rows(len(lanes), self.MIN_VMAP_LANES))
                pidxs = [i for i, *_ in lanes]
                stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
                params = stack([entries[i].params for i in pidxs])
                opt_state = stack([entries[i].opt_state for i in pidxs])
                prev = stack([entries[i].prev_params if use_l else entries[i].params for i in pidxs])
                step0 = jnp.asarray(np.array([entries[i].step for i in pidxs], np.int32))
                feats = {k: jnp.stack([f[k] for _, f, *_ in lanes]) for k in lanes[0][1]}
                labels = jnp.stack([l for _, _, l, *_ in lanes])
                et = jnp.stack([e for _, _, _, e, *_ in lanes])
                idx_mat = jnp.asarray(np.stack([m for _, _, _, _, m, _, _ in lanes]))
                valid = jnp.asarray(np.stack([v for _, _, _, _, _, v, _ in lanes]))
                na = jnp.asarray(np.array([n_active_list[i] for i in pidxs], np.int32))
                params, opt_state, step0, feats, labels, et, prev, idx_mat, valid, na = _shard_lane_trees(
                    len(lanes), params, opt_state, step0, feats, labels, et, prev, idx_mat, valid, na,
                )
            with obs.span("trainer.dispatch"):
                new_params, new_opt = self._train_scan_many(
                    params, opt_state, step0, feats, labels, et, prev, idx_mat, valid, na,
                    use_lucir=use_l, use_thrash=use_thrash,
                )
            # only the real lanes (padding replicas of lane 0 are discarded)
            for j, (i, *_, n_steps) in zip(range(len(idxs)), lanes):
                entries[i].params = jax.tree.map(lambda x: x[j], new_params)
                entries[i].opt_state = jax.tree.map(lambda x: x[j], new_opt)
                entries[i].step += n_steps
                entries[i].n_updates += 1
        return entries


@dataclasses.dataclass
class RunResult:
    top1: float
    per_group: list
    n_classes: int
    n_models: int
    n_samples: int
    predictions: np.ndarray  # predicted class id per sample
    t_index: np.ndarray
    correct: np.ndarray


def run_protocol(
    trace: Trace,
    pcfg: PredictorConfig,
    tcfg: TrainConfig,
    *,
    mode: str = "ours",
    kind: str = "transformer",
    in_et_flags: np.ndarray | None = None,  # per-access E∪T membership (thrash term)
    table: ModelTable | None = None,
) -> RunResult:
    assert mode in ("online_single", "online_multi", "ours", "offline")
    trainer = Trainer(pcfg, tcfg, kind)
    vocab = DeltaVocab(pcfg.delta_vocab)
    stream = FeatureStream(trace, vocab, pcfg.history, page_vocab=pcfg.page_vocab, pc_vocab=pcfg.pc_vocab, tb_vocab=pcfg.tb_vocab)
    classifier = PatternClassifier()

    if mode == "offline":
        fs = stream.windows(0, len(trace))
        n_active = max(vocab.n_classes, 2)
        rng = np.random.default_rng(tcfg.seed)
        train_idx = rng.permutation(len(fs))[: len(fs) // 2]
        entry = Entry(params=trainer.new_params(tcfg.seed))
        sub = fs.slice(0, len(fs))  # full; train on the random half
        half = FeatureSet(*(getattr(fs, f.name)[train_idx] for f in dataclasses.fields(fs)))
        for _ in range(3):  # extra passes — it has future knowledge anyway
            entry = trainer.train_group(entry, half, n_active)
        correct, pred = trainer.evaluate(entry.params, fs, n_active)
        return RunResult(float(correct.mean()), [float(correct.mean())], vocab.n_classes, 1, len(fs), pred, fs.t_index, correct)

    if table is None:
        table = ModelTable(lambda s: trainer.new_params(s), n_slots=tcfg.table_slots)
    multi = mode in ("online_multi", "ours")
    use_lucir = mode == "ours"

    n = len(trace)
    G = tcfg.group_size
    per_group = []
    all_correct = np.zeros(0, bool)
    all_pred = np.zeros(0, np.int32)
    all_t = np.zeros(0, np.int32)
    for g0 in range(0, n, G):
        g1 = min(g0 + G, n)
        fs = stream.windows(g0, g1)
        if len(fs) == 0:
            continue
        n_active = max(vocab.n_classes, 2)
        pat = classifier.classify(trace.block[g0:g1], trace.kernel[g0:g1]) if multi else 0
        entry = table.get(pat)
        correct, pred = trainer.evaluate(entry.params, fs, n_active)  # predict BEFORE training
        per_group.append(float(correct.mean()))
        all_correct = np.concatenate([all_correct, correct])
        all_pred = np.concatenate([all_pred, pred])
        all_t = np.concatenate([all_t, fs.t_index])
        if use_lucir:
            table.snapshot_prev(pat)
            entry = table.get(pat)
        in_et = in_et_flags[fs.t_index] if in_et_flags is not None and mode == "ours" else None
        entry = trainer.train_group(entry, fs, n_active, in_et=in_et, use_lucir=use_lucir)
        table.put(pat, entry)

    top1 = float(all_correct.mean()) if len(all_correct) else 0.0
    return RunResult(top1, per_group, vocab.n_classes, table.n_models, len(all_correct), all_pred, all_t, all_correct)
