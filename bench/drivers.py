"""The one traffic generator: a configuration, a traffic mix and a seed
make the inputs, and the mix's ``driver`` names the entry the window
drives.

* ``learned``: ``runtime.run_ours`` over each workload in turn, a fresh
  manager per workload from the pretrained table, passes repeated until
  the window closes (at the first workload boundary after ``seconds``,
  and never before one whole pass).  A round runs from one ``observe`` call to the next
  (the last round of a workload to the return of ``run_ours`` with its
  fine-tuned entries on the device finished), so every moment of a
  workload belongs to exactly one round.
* ``sweep``: ``simulator.run_batch`` over the mix's lanes, once per
  workload, passes repeated until the window closes.

Each driver records, while the window runs, what the checks afterwards
compare: the first pass's answers in full, and a reference to every later
run's answers, digested only after the window has closed (the program is
deterministic, so every run must give its workload's first answers).
Program methods are wrapped from outside; nothing of the program is
edited.

A traced window (``traced=True``) is the mix's ``trace_seconds`` of the
same traffic: it closes at the first round (learned) or call (sweep)
boundary after that many seconds, so the profiler records all of it.
"""
from __future__ import annotations

import contextlib
import inspect
import time
from unittest import mock

import numpy as np

from bench import tables
from bench.spans import Spans

tables.ensure_src_on_path()


def make_traces(cfg: dict, seed: int) -> list:
    """The configuration's workloads at its scale and cap, each generator's
    own seed offset by ``seed``; a workload of several parts is their
    Section V-F merge in slices of ``slice_len`` accesses, ordered by
    ``seed``."""
    from repro.uvm import trace as T

    def part(name):
        gen = T.BENCHMARKS[name]
        tr = gen(scale=cfg["scale"], seed=inspect.signature(gen).parameters["seed"].default + seed)
        return tr.slice(0, min(len(tr), cfg["cap"]))

    out = []
    for names in cfg["workloads"]:
        if len(names) == 1:
            out.append(part(names[0]))
        else:
            out.append(T.concurrent([part(n) for n in names], seed=seed, slice_len=cfg["slice_len"]))
    return out


def bucket_blocks(n_valid: int) -> int:
    """The device width the program sizes its state to: the next power of
    two of the 2 MB-chunk-padded block count, at least 128."""
    padded = -(-n_valid // 32) * 32
    return max(1 << (padded - 1).bit_length(), 128)


def capacity_for(n_blocks: int, oversubscription: float) -> int:
    return max(int(np.floor(n_blocks / oversubscription)), 1)


class _WindowClosed(Exception):
    """Raised at a round boundary when a traced window's time is up."""


class LearnedDriver:
    """``runtime.run_ours``, one fresh manager per workload."""

    SYNCED = ("simulator.run_segment",)  # spans whose calls wait for their device results

    def __init__(self, cfg: dict, traffic: dict, seed: int, spans: Spans):
        from repro.core.incremental import Trainer

        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.pcfg, self.tcfg = tables.predictor_config(cfg), tables.train_config(cfg)
        self.traces = make_traces(cfg, seed)
        self.trainer = Trainer(self.pcfg, self.tcfg)
        self.table = None
        G = self.tcfg.group_size
        rounds = [(w, r) for w, tr in enumerate(self.traces) for r in range(-(-len(tr) // G))]
        rng = np.random.default_rng([seed, 1])
        pick = lambda k: {rounds[i] for i in rng.choice(len(rounds), size=min(k, len(rounds)), replace=False)}
        self.eval_sample = pick(traffic["sample"]["evaluate"])
        self.train_sample = pick(traffic["sample"]["train"])
        self.first: dict = {}  # workload -> its first run in the window, in full
        self.runs: list = []  # (workload, answers) of every whole run in the window
        self.evals: list = []
        self.trains: list = []
        self.tables_log: dict = {}
        self.counters = {"rounds": 0, "accesses": 0, "eval_samples": 0, "train_steps": 0,
                         "lucir_steps": 0, "workload_runs": 0}
        self.workload_s: list = []  # [workload, seconds, rounds, slowest round in s] per run
        self.record_s = 0.0  # window seconds spent keeping the first pass's answers for the checks
        self._at = None  # (workload, round, recording, counting) of the round in flight
        self._close_at = None  # a traced window's end, checked at each round's start
        self._rounds_before = 0  # rounds counted before this window

    # -- inputs and set-up ----------------------------------------------------

    def setup(self, warm: bool = True) -> None:
        self.table = tables.load_table(tables.table_path(self.cfg), self.trainer)
        if warm:
            with self._hooks():
                for w in range(len(self.traces)):  # one pass: every shape the window uses
                    self._run_one(w, record=False, count=False)

    def _manager(self, trace):
        from repro.uvm import runtime as R

        kw = dict(oversubscription=self.cfg["oversubscription"], table=self.table.clone())
        if trace.tenant is None:
            return R.manager_for(trace, self.pcfg, self.tcfg, **kw)
        return R.mux_for(trace, self.pcfg, self.tcfg, **kw)

    @staticmethod
    def _managers(mgr) -> list:
        return list(mgr.managers.values()) if hasattr(mgr, "managers") else [mgr]

    # -- the window -------------------------------------------------------------

    def window(self, seconds: float, traced: bool = False) -> dict:
        latencies = []
        start = dict(self.counters)
        self._rounds_before = start["rounds"]
        with self._hooks():
            t0 = time.perf_counter()
            deadline = t0 + seconds
            if traced:
                self._close_at = t0 + self.traffic["trace_seconds"]
            t_end = t0
            passes = 0
            while not passes or (t_end < deadline and not traced):
                for w in range(len(self.traces)):
                    if passes and time.perf_counter() >= deadline:
                        break
                    lat, whole = self._run_one(w, record=w not in self.first, count=True)
                    latencies += lat
                    t_end = time.perf_counter()
                    if not whole:
                        break
                else:
                    passes += 1
                    continue
                break
            self._close_at = None
        window_s = t_end - t0
        return {"window_s": window_s, "latencies_s": latencies,
                "learned_accesses_per_s": (self.counters["accesses"] - start["accesses"]) / window_s,
                "round_p95_ms": float(np.percentile(np.asarray(latencies) * 1e3, 95))}

    def _run_one(self, w: int, *, record: bool, count: bool) -> tuple[list, bool]:
        """One workload through ``run_ours``: the latency of each round, and
        whether the workload ran whole (a traced window may close first)."""
        import jax

        from repro.uvm import runtime as R

        trace = self.traces[w]
        t_start = time.perf_counter()
        with self.spans("runtime.new_manager"):
            mgr = self._manager(trace)
        starts, actions, outs = [], [], []
        observe, feedback = mgr.observe, mgr.feedback
        round_span = []

        def timed_observe(batch):
            now = time.perf_counter()
            ran = self.counters["rounds"] - self._rounds_before + len(starts)  # rounds in this window so far
            if self._close_at is not None and now >= self._close_at and ran:
                raise _WindowClosed
            if round_span:
                round_span.pop().__exit__(None, None, None)
            starts.append(now)
            round_span.append(self.spans.open("runtime.round"))
            self._at = (w, len(starts) - 1, record, count)
            with self.spans("manager.observe"):
                a = observe(batch)
            actions.append(a)
            return a

        def timed_feedback(outcomes):
            with self.spans("manager.feedback"):
                return feedback(outcomes)

        mgr.observe, mgr.feedback = timed_observe, timed_feedback
        self._outs = outs
        try:
            res, whole = R.run_ours(trace, manager=mgr), True
        except _WindowClosed:  # every round in ``starts`` ran whole
            res, whole = None, False
        jax.block_until_ready([e.params for m in self._managers(mgr) for e in m.table.slots.values()])
        end = time.perf_counter()
        if round_span:
            round_span.pop().__exit__(None, None, None)
        self._at = None
        lat = list(np.diff(np.asarray(starts + [end])))
        if count:
            self.counters["rounds"] += len(starts)
            self.counters["accesses"] += len(trace) if whole else len(starts) * self.tcfg.group_size
            self.counters["workload_runs"] += whole
            self.workload_s.append([w, end - t_start, len(starts), float(max(lat, default=0.0))])
        if whole:
            if record:
                self.first[w] = {"stats": res.stats, "actions": actions, "outs": outs, "top1": res.top1}
            if count:
                self.runs.append((w, (res.stats, actions, outs)))
        return lat, whole

    @contextlib.contextmanager
    def _hooks(self):
        """Wrap the trainer, simulator and table calls of every round."""
        from repro.core.incremental import Trainer
        from repro.core.policy import PredictionFrequencyTable as Table
        from repro.uvm import simulator as S

        evaluate, train_group = Trainer.evaluate, Trainer.train_group
        run_segment, apply_prefetch = S.run_segment, S.apply_prefetch
        update, on_intervals, dense = Table.update, Table.on_intervals, Table.dense
        drv = self

        def w_evaluate(self, params, fs, n_active):
            with drv.spans("trainer.evaluate"):
                correct, pred = evaluate(self, params, fs, n_active)
            if drv._at is not None:
                w, r, rec, count = drv._at
                drv.counters["eval_samples"] += len(fs) if count else 0
                if rec and (w, r) in drv.eval_sample:
                    t = time.perf_counter()
                    drv.evals.append({"at": (w, r), "params": params, "fs": fs, "n_active": int(n_active),
                                      "pred": pred})
                    drv.record_s += time.perf_counter() - t
            return correct, pred

        def w_train_group(self, entry, fs, n_active, *, in_et=None, use_lucir=False, rng=None):
            before = (entry.params, entry.opt_state, entry.step, entry.prev_params)
            with drv.spans("trainer.train_group"):
                out = train_group(self, entry, fs, n_active, in_et=in_et, use_lucir=use_lucir, rng=rng)
            if drv._at is not None and len(fs):
                use_l = bool(use_lucir and before[3] is not None)
                w, r, rec, count = drv._at
                steps = (out.step - before[2]) if count else 0
                drv.counters["train_steps"] += steps
                drv.counters["lucir_steps"] += steps if use_l else 0
                if rec and (w, r) in drv.train_sample:
                    t = time.perf_counter()
                    opt = before[1] if before[1] is not None else self.opt.init(before[0])
                    drv.trains.append({"at": (w, r), "params": before[0], "m": opt.m, "v": opt.v,
                                       "step": before[2], "prev": before[3] if use_l else before[0],
                                       "use_lucir": use_l, "fs": fs, "n_active": int(n_active),
                                       "in_et": None if in_et is None else np.asarray(in_et),
                                       "new": out.params})
                    drv.record_s += time.perf_counter() - t
            return out

        def w_run_segment(*a, **k):
            with drv.spans("simulator.run_segment"):
                st, outs = run_segment(*a, **k)
            if drv._at is not None:
                drv._outs.append(outs)
            return st, outs

        def w_apply_prefetch(*a, **k):
            with drv.spans("simulator.apply_prefetch"):
                return apply_prefetch(*a, **k)

        def log(table, op):
            t = time.perf_counter()
            drv.tables_log.setdefault((drv._at[0], id(table)), []).append(op())
            drv.record_s += time.perf_counter() - t

        def w_update(self, blocks):
            if drv._at is not None and drv._at[2]:
                log(self, lambda: ("update", np.array(blocks, np.int64).ravel()))
            return update(self, blocks)

        def w_on_intervals(self, n):
            if drv._at is not None and drv._at[2]:
                log(self, lambda: ("flush", int(n)))
            return on_intervals(self, n)

        def w_dense(self, n_blocks):
            out = dense(self, n_blocks)
            if drv._at is not None and drv._at[2]:
                log(self, lambda: ("dense", int(n_blocks), out))
            return out

        with mock.patch.object(Trainer, "evaluate", w_evaluate), \
                mock.patch.object(Trainer, "train_group", w_train_group), \
                mock.patch.object(S, "run_segment", w_run_segment), \
                mock.patch.object(S, "apply_prefetch", w_apply_prefetch), \
                mock.patch.object(Table, "update", w_update), \
                mock.patch.object(Table, "on_intervals", w_on_intervals), \
                mock.patch.object(Table, "dense", w_dense):
            yield

    def release(self) -> None:
        """Drop the program's state that the checks do not read."""
        self.table = None


class SweepDriver:
    """``simulator.run_batch`` over the mix's lanes, one call per workload."""

    SYNCED = ("simulator.run_batch",)

    def __init__(self, cfg: dict, traffic: dict, seed: int, spans: Spans):
        self.cfg, self.traffic, self.seed, self.spans = cfg, traffic, seed, spans
        self.traces = make_traces(cfg, seed)
        self.lanes = [tuple(c) for c in traffic["lanes"]]
        rng = np.random.default_rng([seed, 2])
        k = min(traffic["sample"]["workloads"], len(self.traces))
        self.check_sample = sorted(int(i) for i in rng.choice(len(self.traces), size=k, replace=False))
        self.first: dict = {}
        self.runs: list = []
        self.counters = {"calls": 0, "accesses": 0, "lane_accesses": 0, "lane_events": 0}
        self._counting = False
        from repro.distributed.compat import lane_shardings

        self.sharded = lane_shardings(len(self.lanes))[0] is not None  # lanes spread over the chips

    def setup(self, warm: bool = True) -> None:
        from repro.uvm import simulator as S

        if warm:
            with self._hooks():
                for tr in self.traces:  # one pass: every shape the window uses
                    S.run_batch(tr, self.lanes)

    @contextlib.contextmanager
    def _hooks(self):
        from repro.uvm import simulator as S

        compress = S.compress_events
        drv = self

        def w_compress(*a, **k):
            ev = compress(*a, **k)
            if drv._counting:
                drv.counters["lane_events"] += len(ev.blk) * len(drv.lanes)
            return ev

        with mock.patch.object(S, "compress_events", w_compress):
            yield

    def window(self, seconds: float, traced: bool = False) -> dict:
        from repro.uvm import simulator as S

        start = dict(self.counters)
        with self._hooks():
            t0 = time.perf_counter()
            deadline = t0 + (self.traffic["trace_seconds"] if traced else seconds)
            t_end = t0
            passes = 0
            while not passes or t_end < deadline:
                for w, tr in enumerate(self.traces):
                    if (passes or traced and self.counters["calls"] > start["calls"]) and t_end >= deadline:
                        break
                    self._counting = True
                    with self.spans("simulator.run_batch"):
                        res = S.run_batch(tr, self.lanes)
                    self._counting = False
                    t_end = time.perf_counter()
                    self.counters["calls"] += 1
                    self.counters["accesses"] += len(tr)
                    self.counters["lane_accesses"] += len(tr) * len(self.lanes)
                    self.first.setdefault(w, res)
                    self.runs.append((w, res))
                passes += 1
        window_s = t_end - t0
        return {"window_s": window_s,
                "sweep_accesses_per_s": (self.counters["lane_accesses"] - start["lane_accesses"]) / window_s}

    def release(self) -> None:
        pass


DRIVERS = {"learned": LearnedDriver, "sweep": SweepDriver}
