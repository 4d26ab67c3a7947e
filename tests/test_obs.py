"""The program's spans and spanned device-to-host pulls (``repro.obs``),
that every pull of a learned run, a multi-tenant run and a policy sweep
goes through ``obs.to_host``, and that a rerun of a diverged periodic
segment shows as a ``simulator.rerun`` span and adds no pull."""
from __future__ import annotations

import collections
import contextlib
import math
import traceback
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.configs.predictor_paper import CONFIG_QUICK
from repro.core.incremental import TrainConfig
from repro.uvm import runtime as R
from repro.uvm import simulator as S
from repro.uvm import trace as T

TCFG = TrainConfig(group_size=256, epochs=1, batch_size=64)


def _recorded_spans(tmp_path, body) -> list:
    """``(name, stats)`` of every ``repro:`` host event a profiler records
    while ``body`` runs."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    out = []
    with warnings.catch_warnings():  # the profiler's stat type lacks __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for path in tmp_path.rglob("*.xplane.pb"):
            for plane in ProfileData.from_file(str(path)).planes:
                for line in plane.lines:
                    out += [(e.name, dict(e.stats)) for e in line.events if e.name.startswith(obs.PREFIX)]
    return out


def test_span_prefix_and_ids(tmp_path):
    def body():
        with obs.span("runtime.round", round=3):
            with obs.span("trainer.stage"):
                pass

    got = dict(_recorded_spans(tmp_path, body))
    assert got["repro:runtime.round"] == {"round": 3}
    assert got["repro:trainer.stage"] == {}


def test_spanned_keeps_the_function():
    @obs.spanned("manager.observe")
    def f(a, *, b=2):
        """doc"""
        return a + b

    assert f(1, b=5) == 6 and f.__name__ == "f" and f.__doc__ == "doc"


def test_to_host_returns_the_values_and_counts_once(tmp_path):
    x = jnp.arange(12, dtype=jnp.int32).reshape(3, 4) * 7
    want = np.asarray(x)
    got = {}

    def body():
        got["array"] = obs.to_host(x, "test.site")
        got["tree"] = obs.to_host({"a": x, "b": (x[0, 1], x > 3)}, "test.site")

    spans = _recorded_spans(tmp_path, body)
    a, tree = got["array"], got["tree"]
    assert isinstance(a, np.ndarray) and a.dtype == want.dtype and np.array_equal(a, want)
    assert np.array_equal(tree["b"][1], want > 3) and int(tree["b"][0]) == 7
    assert [n for n, _ in spans] == ["repro:sync.test.site"] * 2  # one span per pull


# -- every pull is counted -------------------------------------------------------


@contextlib.contextmanager
def uncounted_pulls():
    """Record every conversion of a ``jax.Array`` to host values that does
    not go through ``obs.to_host``, count the ``obs.to_host`` calls by
    site and every span by name.  On the CPU ``np.asarray`` reads an array
    through the buffer protocol, past ``__array__``, so numpy's own entry
    points are watched too.  Yields ``(seen, syncs, spans)``."""
    from jax._src.array import ArrayImpl

    seen: list = []
    syncs: collections.Counter = collections.Counter()
    spans: collections.Counter = collections.Counter()
    inside = [0]  # > 0 while a conversion runs: what it calls inside is not noted again

    def watched(fn, kind, is_pull=lambda *a: True, counted=False):
        def w(*args, **kw):
            if not inside[0] and not counted and is_pull(*args):
                seen.append((kind, "".join(traceback.format_stack(limit=6)[:-1])))
            inside[0] += 1
            try:
                return fn(*args, **kw)
            finally:
                inside[0] -= 1

        return w

    def to_host(x, site):
        syncs["sync." + site] += 1
        return counted_to_host(x, site)

    def span(name, **ids):
        spans[name] += 1
        return real_span(name, **ids)

    counted_to_host, real_span = watched(obs.to_host, "obs.to_host", counted=True), obs.span
    on_array = lambda a, *_: isinstance(a, jax.Array)
    patches = [(np, n, watched(getattr(np, n), "np." + n, on_array)) for n in ("asarray", "array", "asanyarray")]
    patches += [(ArrayImpl, n, watched(getattr(ArrayImpl, n), n))
                for n in ("__array__", "__int__", "__float__", "__bool__", "__index__", "item", "tolist")]
    patches += [(obs, "to_host", to_host), (obs, "span", span),
                (jax, "device_get", watched(jax.device_get, "jax.device_get"))]
    saved = [(o, n, getattr(o, n)) for o, n, _ in patches]
    for o, n, f in patches:
        setattr(o, n, f)
    try:
        yield seen, syncs, spans
    finally:
        for o, n, f in saved:
            setattr(o, n, f)


def test_the_watch_sees_each_kind_of_pull():
    x = jnp.arange(3) + 1
    with uncounted_pulls() as (seen, syncs, spans):
        np.asarray(x)
        int(x[0])
        bool(x[1] > 0)
        x[2].item()
        jax.device_get(x)
        obs.to_host(x, "test.site")
    assert [k for k, _ in seen] == ["np.asarray", "__int__", "__bool__", "item", "jax.device_get"]
    assert syncs == {"sync.test.site": 1} and spans == {"sync.test.site": 1}


def _periodic_segments(trace, G) -> int:
    """Segments whose periodic compression has aggregates: each waits once
    on the divergence check."""
    b, nxt = trace.block.astype(np.int32), S.next_use_for(trace)
    return sum(bool((S.compress_events(b[g:g + G], nxt[g:g + G], periodic=True).stride > 1).any())
               for g in range(0, len(trace), G))


@pytest.mark.parametrize("kind", ["single", "mux"])
def test_every_pull_of_a_learned_run_is_counted(kind):
    if kind == "single":
        trace = T.BENCHMARKS["StreamTriad"](scale=0.05)
    else:
        parts = [T.BENCHMARKS[n](scale=0.05) for n in ("StreamTriad", "ATAX")]
        trace = T.concurrent([p.slice(0, 1024) for p in parts], slice_len=256)
    rounds = math.ceil(len(trace) / TCFG.group_size)
    with uncounted_pulls() as (seen, syncs, spans):
        R.run_ours(trace, CONFIG_QUICK, TCFG)
    assert not seen, "device-to-host pulls outside obs.to_host:\n" + "\n".join(s for _, s in seen[:3])
    periodic = _periodic_segments(trace, TCFG.group_size)
    # per round: the predictor's output, the segment's three outputs (a
    # rerun pulls them in place of the diverged pass), the fault clock (and
    # the residency mask for the mux), the divergence check where the
    # segment has periodic events; the final counters once per run
    per_round = {"sync.simulator.outs": 3 * rounds, "sync.runtime.fault_count": rounds,
                 "sync.simulator.pfault": periodic, "sync.runtime.stats": 5}
    if kind == "mux":
        per_round["sync.runtime.resident"] = rounds
        tenants_per_round = [len(np.unique(trace.tenant[g:g + TCFG.group_size]))
                             for g in range(0, len(trace), TCFG.group_size)]
        per_round["sync.trainer.evaluate"] = sum(tenants_per_round)
    else:
        per_round["sync.trainer.evaluate"] = rounds
    assert syncs == {k: v for k, v in per_round.items() if v}
    assert periodic > 0  # the trace exercises the divergence check
    assert spans["runtime.round"] == rounds and spans["simulator.run_segment"] == rounds


def test_every_pull_of_a_sweep_is_counted():
    trace = T.BENCHMARKS["AddVectors"](scale=0.05)
    cells = [("lru", "demand", 1.25), ("hpe", "tree", 1.5), ("learned", "demand", 1.25)]
    with uncounted_pulls() as (seen, syncs, spans):
        S.run_batch(trace, cells)
    assert not seen, "device-to-host pulls outside obs.to_host:\n" + "\n".join(s for _, s in seen[:3])
    periodic = _periodic_segments(trace, len(trace))
    want = {"sync.simulator.pfault": periodic, "sync.simulator.counters": 1}
    assert syncs == {k: v for k, v in want.items() if v}


# -- a rerun is spanned and adds no pull -------------------------------------------

SWEEP_TRACE = lambda: T.BENCHMARKS["AddVectors"](scale=0.05)  # noqa: E731  (periodic events: see above)


def _sim_call(kind: str):
    """A simulator call over a trace with periodic events, how many
    divergence checks it pulls and how many reruns a divergence in every
    lane makes.  The call returns its answers: counters, and per-access
    outputs where the call has them."""
    trace = SWEEP_TRACE()
    cells = [("lru", "tree", 1.25), ("hpe", "demand", 1.5), ("lru", "demand", 1.25), ("hpe", "tree", 1.25)]
    if kind == "batch":
        return (lambda: S.run_batch(trace, cells)), 1, 1  # every lane reruns in one pass
    nb = S.bucket_blocks(trace.n_blocks)
    blocks, nxt = trace.block.astype(np.int32), S.next_use_for(trace)
    answers = lambda st, outs: ([getattr(st, k) for k in ("faults", "thrash_events", "migrations", "resident")],  # noqa: E731
                                outs)
    if kind == "segment":
        def call():
            return answers(*S.run_segment(S.init_state(nb), blocks, nxt, capacity=S.capacity_for(trace.n_blocks, 1.25),
                                          policy="lru", prefetch="tree", n_valid=trace.n_blocks))
        return call, 1, 1
    n = int(kind.rsplit("-", 1)[1])  # segments_many-<lanes>: under 4 lanes each runs alone
    ids = [(S.POLICY_IDS[p], S.PREFETCH_IDS[f], S.capacity_for(trace.n_blocks, o)) for p, f, o in cells[:n]]

    def call():
        out = S.run_segments_many([S.init_state(nb) for _ in ids], [(blocks, nxt)] * n, ids, [trace.n_blocks] * n)
        return [answers(st, o) for st, o in out]
    return call, (1 if n >= 4 else n), n  # 4 lanes: one scan, one check, each lane reruns alone


def _forced_divergence(to_host):
    """``obs.to_host`` that reports every periodic pass as diverged."""
    def forced(x, site):
        got = to_host(x, site)
        return np.ones_like(got) if site == "simulator.pfault" else got
    return forced


@pytest.mark.parametrize("kind", ["segment", "segments_many-1", "segments_many-4", "batch"])
def test_a_forced_rerun_is_spanned_adds_no_pull_and_keeps_the_answers(kind, monkeypatch):
    call, checks, reruns = _sim_call(kind)
    with uncounted_pulls() as (seen, syncs, spans):
        plain = call()
    assert spans["simulator.rerun"] == 0 and syncs["sync.simulator.pfault"] == checks
    with uncounted_pulls() as (seen_f, syncs_f, spans_f):
        monkeypatch.setattr(obs, "to_host", _forced_divergence(obs.to_host))
        forced = call()
    assert not seen and not seen_f
    assert spans_f["simulator.rerun"] == reruns
    assert spans_f["simulator.compress"] == spans["simulator.compress"] + spans_f["simulator.rerun"]
    assert syncs_f == syncs  # the rerun pulls the outputs the diverged pass did not
    flat = lambda t: jax.tree.leaves(jax.tree.map(np.asarray, t))  # noqa: E731
    assert all(np.array_equal(a, b) for a, b in zip(flat(plain), flat(forced), strict=True))
