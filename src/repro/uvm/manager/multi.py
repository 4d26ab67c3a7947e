"""`TenantMux` — multi-tenant streaming oversubscription management.

The paper's headline accuracy result covers *multiple concurrent GPGPU
workloads* (Section V-F: +10.2% top-1 on average, up to +30.2%): when
tenants share a GPU, one classifier->predictor pipeline over the MERGED
fault stream blends pattern classes inside every observation window and
the per-workload delta structure drowns.  The fix is per-workload
specialization: demultiplex the tenant-tagged fault stream into one
:class:`~repro.uvm.manager.OversubscriptionManager` per tenant, each with
its own classifier state, delta vocabulary, window history and per-pattern
model table, while the device-wide artifacts (the dense prediction
frequency export the `learned` eviction policy reads, the staged prefetch
set) are combined across tenants.

Protocol — the manager's stepwise rounds, lifted to a tagged stream::

    mux = TenantMux(cfg, tenants=("A", "B"))
    out = mux.observe(FaultBatch(page=pages, tenant=tags))   # demux -> per-tenant pipelines
    ... stage out.prefetch_blocks / out.counters ...
    mux.feedback(Outcomes(was_evicted=..., fault_count=...)) # split back per tenant

* ``observe`` splits the batch by tag (within-tenant order preserved),
  runs each present tenant's ``observe_begin``, batches every predictor
  dispatch through ONE ``Trainer.evaluate_many`` call, and combines the
  per-tenant actions into a :class:`MuxActions`.
* ``feedback`` splits ``was_evicted`` back along the same partition and
  forwards the GLOBAL fault clock to every tenant observed this round
  (each manager's 3-interval flush cadence advances on the device-wide
  far-fault count; absent tenants catch up on their next observation).
  ``feedback(..., tenant=k)`` instead closes tenant ``k``'s pending batch
  explicitly — the ``cli serve`` sidecar's per-line pairing.
* the staged halves (``observe_begin/observe_finish``,
  ``feedback_begin/feedback_finish``) return per-tenant request lists so
  lockstep drivers (``runtime.run_ours_many``) can batch model dispatches
  across lanes AND tenants in one vmapped call.

Frequency-table topology is configurable: ``shared_freq_table=False``
(default) gives every tenant an ISOLATED table — with it, demuxing a
:func:`repro.uvm.trace.concurrent` merge is exactly equivalent to running
each tenant's stream through its own standalone manager (property-pinned
in tests/test_multi.py); ``shared_freq_table=True`` makes all tenants
update ONE table (the paper's single 18KB SRAM budget, Section IV-D).
Tenants always share one :class:`~repro.core.incremental.Trainer` (jit
caches), never model state.
"""
from __future__ import annotations

import dataclasses
import pickle

import numpy as np

from repro import obs
from repro.core.incremental import Trainer
from repro.core.model_table import ModelTable
from repro.uvm import registry as _registry
from repro.uvm.manager.core import (
    INTERVAL_FAULTS,
    Actions,
    EvalRequest,
    FaultBatch,
    ManagerConfig,
    Outcomes,
    OversubscriptionManager,
    TrainRequest,
    _cfg_signature,
)
from repro.uvm.manager.snapshot import STATE_VERSION

_UNSET = object()


@dataclasses.dataclass
class MuxActions:
    """One round's combined output: the device-wide artifacts a simulator
    (or any residency engine) stages, plus every tenant's own
    :class:`~repro.uvm.manager.Actions` for per-workload consumers.

    ``counters`` is the combined dense prediction-frequency export
    (elementwise max across tenant tables — tenants occupy disjoint page
    ranges, so the max is the union; one table serves directly when
    shared); ``None`` when no tenant's prefetch gate opened this round,
    matching the single-manager cadence (a stale export stays staged).
    ``pre_evict_blocks`` round-robins the tenants' advisory rankings so no
    tenant's victims dominate the head.

    ``budgets`` is the QoS capacity partition this round was observed
    under (tenant -> blocks), ``None`` on muxes without a budget
    controller — consumers that want the eviction-tier artifact itself
    call :meth:`TenantMux.evict_pref` with their residency mask."""

    per_tenant: dict
    prefetch_blocks: np.ndarray
    counters: np.ndarray | None
    pre_evict_blocks: np.ndarray
    budgets: dict | None = None

    @property
    def patterns(self) -> dict:
        return {k: a.pattern for k, a in self.per_tenant.items()}


def _stable_unique(parts: list[np.ndarray]) -> np.ndarray:
    """Concatenate + dedup preserving first-appearance order."""
    if not parts:
        return np.zeros(0, np.int64)
    cat = np.concatenate([np.asarray(p, np.int64) for p in parts])
    _, first = np.unique(cat, return_index=True)
    return cat[np.sort(first)]


def _round_robin(parts: list[np.ndarray]) -> np.ndarray:
    """Interleave the tenants' rankings fairly (worst-first per tenant)."""
    parts = [np.asarray(p, np.int64) for p in parts if len(p)]
    if not parts:
        return np.zeros(0, np.int64)
    width = max(len(p) for p in parts)
    out = [p[i] for i in range(width) for p in parts if i < len(p)]
    return _stable_unique([np.asarray(out, np.int64)])


class _SharedFreqTableView:
    """The shared frequency table as ONE tenant manager sees it: reads and
    updates pass through, but ``on_intervals`` is a no-op — the flush
    cadence is owned by the mux.  (Every manager computes the same
    device-interval delta from the global fault clock; letting each apply
    it would flush the one table N_tenants times per interval.)"""

    def __init__(self, table):
        self._table = table

    def update(self, blocks):
        self._table.update(blocks)

    def lookup(self, block):
        return self._table.lookup(block)

    def lookup_many(self, blocks):
        return self._table.lookup_many(blocks)

    def dense(self, n_blocks):
        return self._table.dense(n_blocks)

    def on_intervals(self, n):  # mux-owned (see TenantMux._advance_shared_clock)
        pass

    @property
    def tags(self):
        return self._table.tags

    @property
    def counters(self):
        return self._table.counters

    @property
    def flushes(self):
        return self._table.flushes


class TenantMux:
    """Demultiplex a tenant-tagged fault stream into per-tenant
    classifier->predictor pipelines (module docs have the protocol).

    ``tenants`` pre-declares the tenant keys (any hashables that survive a
    numpy equality test against the tag array — ints for trace merges,
    strings for the serve sidecar).  ``auto_create=True`` (the default)
    admits unseen tags by building their manager on first contact — the
    endless-stream sidecar mode; pass ``False`` to make an unknown tag a
    hard ``KeyError`` (the trace drivers, where the tenant set is known).

    ``tables`` seeds each tenant's per-pattern model table: a dict keyed
    by tenant, or ONE Section V-A pretrained master that every tenant
    clones (fine-tuning mutates entries — tenants must not share them).

    ``qos`` attaches a :class:`repro.uvm.qos.BudgetController`: every
    observed batch claims its tenant's blocks (first-toucher ownership),
    every feedback round feeds the tenant's thrash rate into the elastic
    rebalance, and :meth:`evict_pref` compiles the current budgets into
    the simulator's leading victim key.  ``None`` (default) = today's
    shared pool, bit-identical.
    """

    def __init__(
        self,
        cfg: ManagerConfig,
        tenants=(),
        *,
        shared_freq_table: bool = False,
        auto_create: bool = True,
        tables: dict | ModelTable | None = None,
        trainer: Trainer | None = None,
        qos=None,
    ):
        self.cfg = cfg
        self.shared_freq_table = shared_freq_table
        self.auto_create = auto_create
        self._tables = tables
        self.trainer = trainer if trainer is not None else Trainer(cfg.predictor, cfg.train, cfg.kind)
        self._shared_freq = _registry.freq_table_factory(cfg.freq_table)() if shared_freq_table else None
        self.qos = qos
        self.managers: dict = {}
        # released tenants' final stats, so departure doesn't erase them
        # from the run-level result views below
        self._departed: dict = {}
        self.per_group: list[float] = []  # batch accuracies in dispatch order
        self._round: list[tuple] | None = None  # [(tenant, positions, n)], last observe's split
        self._last_feedback: list[tuple] = []  # feedback_begin's pairs, for feedback_finish
        # mux-owned flush cadence for the SHARED table (managers hold
        # no-flush views); same rebase rule as the per-manager clock
        self._fault_base = 0
        self._fault_raw = 0
        self._flush_interval = 0
        for t in tenants:
            self._create(t)

    # -- tenant admission ----------------------------------------------------

    def _create(self, key) -> OversubscriptionManager:
        table = self._tables
        if isinstance(table, dict):
            table = table.get(key)
        elif isinstance(table, ModelTable):
            table = table.clone()  # one warm master, private per-tenant copies
        mgr = OversubscriptionManager(
            self.cfg, table=table, trainer=self.trainer,
            freq_table=_SharedFreqTableView(self._shared_freq) if self._shared_freq is not None else None,
        )
        self.managers[key] = mgr
        return mgr

    def tenant(self, key) -> OversubscriptionManager:
        """The tenant's manager (admitting the key if ``auto_create``)."""
        if key not in self.managers:
            if not self.auto_create:
                raise KeyError(f"unknown tenant {key!r}; declared: {list(self.managers)}")
            self._create(key)
        return self.managers[key]

    def release(self, key) -> None:
        """Retire a departed tenant: drop its manager so its (stale)
        frequency counters leave :meth:`_combined_dense`'s per-tenant max,
        and return its QoS claim so budgets rebalance to live tenants.
        A churned trace's early-leaving tenant would otherwise hold rows
        in the combined dense export — and a budget slice — forever.
        Idempotent; a re-appearing tag is re-admitted fresh.  The departed
        tenant's accuracy/model counts are retained so the run-level
        result views still cover it."""
        m = self.managers.pop(key, None)
        if m is not None:
            self._departed[key] = {
                "corr": (m._corr_true, m._corr_n), "warm": (m._warm_true, m._warm_n),
                "top1": m.top1, "n_predictions": m.n_predictions,
                "n_classes": m.n_classes, "n_models": m.n_models,
            }
        if self.qos is not None:
            self.qos.release(key)
        if self._round is not None:
            self._round = [r for r in self._round if r[0] != key] or None

    def _split(self, batch: FaultBatch) -> list[tuple]:
        """Partition one batch by tenant tag, first-appearance order,
        within-tenant access order preserved. Untagged batches route to
        the ``'default'`` tenant (the single-workload degenerate case)."""
        tags = batch.tenant
        if tags is None or np.ndim(tags) == 0:
            key = "default" if tags is None else (tags.item() if hasattr(tags, "item") else tags)
            return [(key, np.arange(len(batch)), batch)]
        keys, first = np.unique(tags, return_index=True)
        out = []
        for k in keys[np.argsort(first)]:
            idx = np.flatnonzero(tags == k)
            out.append((
                k.item() if hasattr(k, "item") else k,
                idx,
                FaultBatch(batch.page[idx], batch.pc[idx], batch.tb[idx], batch.kernel[idx]),
            ))
        return out

    # -- streaming protocol --------------------------------------------------

    @obs.spanned("manager.observe")
    def observe(self, batch: FaultBatch) -> MuxActions:
        """One full round: demux, per-tenant classify, ONE batched predictor
        dispatch, combined actions.  With ``cfg.health`` set, each tenant's
        pre-dispatch guard runs first (a tenant with poisoned params falls
        back alone) and a batched-dispatch failure demotes every tenant
        that dispatched — they all fall back this round."""
        pairs, evals = self.observe_requests(batch)
        out: list | BaseException = []
        if evals:
            try:
                out = self.trainer.evaluate_many(
                    [r.params for _, r in evals], [r.fs for _, r in evals],
                    [r.n_active for _, r in evals],
                )
            except Exception as exc:  # noqa: BLE001 — degraded mode absorbs anything
                out = exc
        return self.observe_apply(pairs, evals, out)

    def observe_requests(self, batch: FaultBatch):
        """The dispatch-staging half of :meth:`observe`: demux + classify
        via :meth:`observe_begin`, then run each tenant's pre-dispatch
        health guard.  Returns ``(pairs, evals)`` — all ``(tenant,
        request)`` pairs plus the guarded subset that should actually hit
        the trainer.  A lockstep server batches many muxes' ``evals``
        through ONE ``evaluate_many`` and hands each mux its result slice
        (or the shared exception) back via :meth:`observe_apply`."""
        pairs = self.observe_begin(batch)
        evals = [(k, r) for k, r in pairs if r is not None and self.managers[k].guard_dispatch(r)]
        return pairs, evals

    def observe_apply(self, pairs, evals, out) -> MuxActions:
        """The result-folding half of :meth:`observe`.  ``out`` is
        ``evaluate_many``'s result list aligned with ``evals`` — or the
        exception it raised, which (with ``cfg.health`` set) demotes every
        tenant that dispatched; they all fall back this round."""
        dispatched = {id(r) for _, r in evals}
        if isinstance(out, BaseException):
            if self.cfg.health is None:
                raise out
            for k, _r in evals:
                self.managers[k].note_fault(out)
            out = [None] * len(evals)
        else:
            out = [
                res if self.managers[k].check_result(*res) else None
                for (k, _r), res in zip(evals, out)
            ]
        results = iter(out)
        return self.observe_finish(
            [next(results) if (r is not None and id(r) in dispatched) else None for _, r in pairs]
        )

    @obs.spanned("manager.feedback")
    def feedback(self, outcomes: Outcomes, *, tenant=_UNSET) -> None:
        """Close the last round (or one tenant's pending batch): split the
        outcome report, advance every observed tenant's fault clock, batch
        the fine-tune dispatches through ONE ``train_group_many``.  With
        ``cfg.health`` set, a batched train failure demotes every tenant
        whose fine-tune was staged (their entry updates are lost; the
        rounds still close)."""
        pairs, treqs = self.feedback_requests(outcomes, tenant=tenant)
        exc = None
        # dispatch even with zero staged trains: a chaos-wrapped trainer
        # draws its RNG per CALL, so skipping the empty call would shift
        # every later injection site of a seeded schedule
        try:
            self.trainer.train_group_many(
                [r.entry for _, r in treqs], [r.fs for _, r in treqs],
                [r.n_active for _, r in treqs],
                in_et_list=[r.in_et for _, r in treqs], use_lucir=self.cfg.use_lucir,
            )
        except Exception as e:  # noqa: BLE001
            exc = e
        self.feedback_apply(pairs, treqs, exc)

    def feedback_requests(self, outcomes: Outcomes, *, tenant=_UNSET):
        """The dispatch-staging half of :meth:`feedback`: split the outcome
        report and stage each tenant's fine-tune.  Returns ``(pairs,
        treqs)`` — all ``(tenant, request)`` pairs plus the non-``None``
        subset to hand to ``train_group_many`` (requests carry
        ``use_lucir``; a lockstep server batches them across muxes)."""
        pairs = self.feedback_begin(outcomes, tenant=tenant)
        treqs = [(k, r) for k, r in pairs if r is not None]
        return pairs, treqs

    def feedback_apply(self, pairs, treqs, exc) -> None:
        """The result-folding half of :meth:`feedback`.  ``exc`` is the
        exception ``train_group_many`` raised (entries are updated in
        place, so success carries no payload); with ``cfg.health`` set it
        demotes every tenant whose fine-tune was staged."""
        if exc is not None:
            if self.cfg.health is None:
                raise exc
            for k, _r in treqs:
                self.managers[k].note_fault(exc)
                self.managers[k]._pending = None
            self.feedback_finish([None] * len(pairs))
            return
        self.feedback_finish([r.entry if r is not None else None for _, r in pairs])

    # -- staged halves (lockstep drivers batch across lanes AND tenants) -----

    def observe_begin(self, batch: FaultBatch) -> list[tuple[object, EvalRequest | None]]:
        """Demux + per-tenant ingest/classify; returns ``(tenant, request)``
        pairs in first-appearance order (request ``None`` when that
        tenant's slice yields no window samples)."""
        batch = batch if isinstance(batch, FaultBatch) else FaultBatch(np.asarray(batch))
        split = self._split(batch)
        self._round = [(k, idx, len(idx)) for k, idx, _ in split]
        if self.qos is not None:
            for k, _idx, sub in split:
                self.qos.observe_blocks(
                    k, np.unique(np.asarray(sub.page, np.int64) // self.cfg.pages_per_block))
        return [(k, self.tenant(k).observe_begin(sub)) for k, idx, sub in split]

    def observe_finish(self, results: list) -> MuxActions:
        """Fold each tenant's predictor output; combine the device-wide
        artifacts. ``results`` aligns with ``observe_begin``'s pairs —
        ``(corr, pred_cls)`` per dispatched tenant, ``None`` otherwise."""
        if self._round is None:
            raise RuntimeError("observe_finish() without observe_begin()")
        per_tenant: dict = {}
        for (k, _idx, _n), res in zip(self._round, results):
            corr, pred = res if res is not None else (None, None)
            actions = self.managers[k].observe_finish(corr, pred)
            per_tenant[k] = actions
            if actions.accuracy is not None:
                self.per_group.append(actions.accuracy)
        with obs.span("manager.combine"):
            warm_any = any(a.counters is not None for a in per_tenant.values())
            counters = self._combined_dense() if warm_any else None
            return MuxActions(
                per_tenant=per_tenant,
                prefetch_blocks=_stable_unique([a.prefetch_blocks for a in per_tenant.values()]),
                counters=counters,
                pre_evict_blocks=_round_robin([a.pre_evict_blocks for a in per_tenant.values()]),
                budgets=dict(self.qos.budgets) if self.qos is not None else None,
            )

    def feedback_begin(self, outcomes: Outcomes, *, tenant=_UNSET) -> list[tuple[object, TrainRequest | None]]:
        """Split the outcome report along the last round's partition (or
        hand it whole to one tenant) and stage each fine-tune dispatch."""
        self._advance_shared_clock(outcomes)
        if tenant is not _UNSET:
            out = [(tenant, self.tenant(tenant).feedback_begin(outcomes))]
            # the tenant's slot in a pending round (if any) is now closed —
            # a later round-level feedback must not replay it
            if self._round is not None:
                self._round = [r for r in self._round if r[0] != tenant] or None
            if self.qos is not None:
                we1 = outcomes.was_evicted
                self.qos.observe_pressure(
                    tenant, float(np.mean(we1)) if we1 is not None and len(we1) else 0.0)
                self.qos.step()
            self._last_feedback = out
            return out
        if self._round is None:
            raise RuntimeError("feedback() without a pending observe() round")
        we = None if outcomes.was_evicted is None else np.asarray(outcomes.was_evicted)
        out = []
        for k, idx, n in self._round:
            sub = Outcomes(
                was_evicted=None if we is None else we[idx],
                fault_count=outcomes.fault_count,  # the GLOBAL device clock
            )
            # the tenant's thrash rate this round (its own slice of the
            # report) is the budget controller's pressure signal
            if self.qos is not None:
                sw = sub.was_evicted
                self.qos.observe_pressure(k, float(np.mean(sw)) if sw is not None and len(sw) else 0.0)
            out.append((k, self.managers[k].feedback_begin(sub)))
        if self.qos is not None:
            self.qos.step()
        self._round = None
        self._last_feedback = out
        return out

    def feedback_finish(self, entries: list) -> None:
        """Publish each tenant's fine-tuned entry (aligned with
        ``feedback_begin``'s pairs; ``None`` = nothing was staged)."""
        for (k, _r), entry in zip(self._last_feedback, entries):
            if entry is not None:
                self.managers[k].feedback_finish(entry)

    # -- snapshot / restore --------------------------------------------------

    def state(self) -> dict:
        """Host-side snapshot of the whole mux: the shared frequency table
        (serialized ONCE — per-tenant states skip it), the mux-owned flush
        clock, the dispatch-order accuracy log, and every tenant's manager
        state in admission order.  Snapshots happen at round boundaries:
        raises while an observe round or any tenant batch is pending."""
        if self._round is not None:
            raise RuntimeError("cannot snapshot mid-round; feedback() the open observe first")
        for k, m in self.managers.items():
            if m._pending is not None:
                raise RuntimeError(f"cannot snapshot: tenant {k!r} has a pending batch")
        return {
            "version": STATE_VERSION,
            "cfg_sig": _cfg_signature(self.cfg),
            "shared_freq_table": self.shared_freq_table,
            "shared_freq": pickle.dumps(self._shared_freq) if self._shared_freq is not None else None,
            "clock": (self._fault_base, self._fault_raw, self._flush_interval),
            "per_group": list(self.per_group),
            "qos": self.qos.state() if self.qos is not None else None,
            "departed": {k: dict(v) for k, v in self._departed.items()},
            "tenants": [
                (k, m.state(include_freq_table=self._shared_freq is None))
                for k, m in self.managers.items()
            ],
        }

    def restore(self, state: dict) -> None:
        """Inverse of :meth:`state`: rebuilds every tenant's manager (same
        config, same shared-table topology) and restores each one."""
        if state.get("version") != STATE_VERSION:
            raise ValueError(
                f"snapshot state version {state.get('version')!r} != supported {STATE_VERSION}"
            )
        if state.get("cfg_sig") != _cfg_signature(self.cfg):
            raise ValueError(
                "snapshot was taken under a different ManagerConfig; "
                "restore requires an identically-configured mux"
            )
        if state.get("shared_freq_table") != self.shared_freq_table:
            raise ValueError("snapshot and mux disagree on shared_freq_table topology")
        if state["shared_freq"] is not None:
            self._shared_freq = pickle.loads(state["shared_freq"])
        self._fault_base, self._fault_raw, self._flush_interval = state["clock"]
        self.per_group = list(state["per_group"])
        # pre-QoS snapshots carry no "qos" entry; a budgeted mux restores
        # its controller only when the snapshot recorded one
        if self.qos is not None and state.get("qos") is not None:
            self.qos.restore(state["qos"])
        self._departed = {k: dict(v) for k, v in state.get("departed", {}).items()}
        self.managers = {}
        for k, mstate in state["tenants"]:
            self._create(k).restore(mstate)  # views rebind to the restored shared table
        self._round = None
        self._last_feedback = []

    # -- combined artifacts --------------------------------------------------

    def _advance_shared_clock(self, outcomes: Outcomes) -> None:
        """Advance the mux-owned flush cadence of the SHARED table from the
        global fault clock (one flush check per device interval, however
        many tenants reported it); no-op with isolated tables, where each
        manager owns its table's cadence."""
        if self._shared_freq is None:
            return
        raw = int(outcomes.fault_count)
        if raw < self._fault_raw:  # consumer switch: its clock restarted at 0
            self._fault_base += self._fault_raw
        self._fault_raw = raw
        interval_now = (self._fault_base + raw) // INTERVAL_FAULTS
        if interval_now > self._flush_interval:
            self._shared_freq.on_intervals(interval_now - self._flush_interval)
            self._flush_interval = interval_now

    def _combined_dense(self) -> np.ndarray:
        """Device-wide dense frequency export: the shared table directly,
        or the elementwise max across the isolated per-tenant tables
        (disjoint tenant page ranges make the max a union; -1 = never).
        Only LIVE tenants contribute — :meth:`release` drops a departed
        tenant's manager, so its stale counters stop shadowing the max."""
        nb = self.cfg.n_blocks
        if self._shared_freq is not None:
            return self._shared_freq.dense(nb)
        if not self.managers:
            return np.full(nb, -1, np.int32)  # every tenant released
        return np.maximum.reduce([m.freq_table.dense(nb) for m in self.managers.values()])

    def evict_pref(self, resident) -> np.ndarray | None:
        """The QoS leading victim key for ``resident`` (the simulator's
        bool residency mask) — ``None`` without a budget controller, which
        keeps budget-free drivers on the exact pre-QoS compiled path."""
        return None if self.qos is None else self.qos.evict_pref(resident)

    # -- result views (the shapes LearnedRunResult aggregates) ---------------

    @property
    def top1(self) -> float:
        t = sum(m._corr_true for m in self.managers.values())
        t += sum(d["corr"][0] for d in self._departed.values())
        n = sum(m._corr_n for m in self.managers.values())
        n += sum(d["corr"][1] for d in self._departed.values())
        return t / n if n else 0.0

    @property
    def warm_top1(self) -> float:
        t = sum(m._warm_true for m in self.managers.values())
        t += sum(d["warm"][0] for d in self._departed.values())
        n = sum(m._warm_n for m in self.managers.values())
        n += sum(d["warm"][1] for d in self._departed.values())
        return t / n if n else self.top1

    @property
    def n_predictions(self) -> int:
        return sum(m.n_predictions for m in self.managers.values()) + \
            sum(d["n_predictions"] for d in self._departed.values())

    @property
    def n_classes(self) -> int:
        return sum(m.n_classes for m in self.managers.values()) + \
            sum(d["n_classes"] for d in self._departed.values())

    @property
    def n_models(self) -> int:
        return sum(m.n_models for m in self.managers.values()) + \
            sum(d["n_models"] for d in self._departed.values())

    @property
    def per_tenant_top1(self) -> dict:
        out = {str(k): d["top1"] for k, d in self._departed.items()}
        out.update({str(k): m.top1 for k, m in self.managers.items()})
        return out

    # -- health views (the serve sidecar's summary line) ---------------------

    @property
    def n_health_faults(self) -> int:
        return sum(m.n_health_faults for m in self.managers.values())

    @property
    def n_fallbacks(self) -> int:
        return sum(m.n_fallbacks for m in self.managers.values())

    @property
    def n_recoveries(self) -> int:
        return sum(m.n_recoveries for m in self.managers.values())

    @property
    def health_states(self) -> dict:
        return {str(k): m.health_state for k, m in self.managers.items()}
