"""Trace-driven UVM device-memory simulator (the GPGPU-Sim replacement).

Pure-JAX ``lax.scan`` over the access stream with fixed-size per-block state
arrays (residency, LRU clocks, chain intervals, Belady next-use, learned
prediction frequency). Migration/eviction is at 64KB basic-block granularity
— the CUDA runtime's prefetch unit — and "pages thrashed" are reported as
blocks x 16 pages, matching the granularity of the paper's counters.

Eviction policies (Section II-C / IV-D):
    lru      — least-recently-used (CUDA driver default)
    random   — uniform random resident block
    belady   — MIN oracle (needs the precomputed next-use stream)
    hpe      — page-set chain (new/middle/old by fault interval) + LRU inside
    learned  — page-set chain + prediction-frequency table (the paper's engine)

Prefetchers (Section II-B):
    demand   — migrate only the faulted block
    tree     — NVIDIA tree-based neighbourhood prefetcher: after a migration,
               any [2,4,8,16,32]-block node above 50% valid occupancy gets its
               remaining blocks migrated
    none     — alias of demand; the learned prefetcher stages its blocks via
               :func:`apply_prefetch` between scan segments (async analogue)

Hot-path design — bit-identical to :mod:`repro.uvm.reference` for every
policy except ``random``: the random policy's victim draws are
``fold_in(key, t)`` over the padded block axis, so its draws (and therefore
its counters) depend on the padded state width, which the fast path is free
to change.  That padding-PRNG dependence is the ONE documented divergence;
every other policy's counters, per-access outputs and state arrays are
exact (see tests/test_properties.py and tests/test_sim_equivalence.py).

  * **fault-event compression** — consecutive accesses to the same block
    cannot fault after the first (the block was just migrated and is
    protected during its own step), so the trace is run-length-compressed
    on the host into per-run events carrying aggregate bookkeeping
    (final ``last_access``/``next_use``, pinned ``zero_copy`` mass, the
    interval-boundary fix-up for the page-set chain). The scan length
    shrinks by the repeat-run hit rate (1x-10x on the paper's suite).
  * **period-p event compression** — streaming traces interleave p arrays
    (block stream ``b0 b1 b2 b0 b1 b2 ...``), which plain RLE cannot
    shorten.  Fixed-period windows are detected host-side and each
    position's repeat occurrences merge into one stride-p aggregate event.
    Invariant: once the window's first period has run, a fault-free window
    stays fault-free (no fault => no migration => no eviction => residency
    frozen), so aggregates are pure bookkeeping.  Whether the window IS
    fault-free depends on runtime state, so it is verified in-scan (the
    ``pfault`` output); on divergence the segment transparently reruns on
    plain RLE events.  Compression is thus a pure scan-length optimisation
    with unconditionally exact counters (8x on AddVectors/StreamTriad).
  * **device-sharded sweeps** — multi-lane scans commit their lane axis to
    a 1-D mesh over ``jax.devices()`` when several devices are visible
    (``REPRO_SIM_SHARD=0`` disables); lanes are independent, so GSPMD
    partitions the sweep without communication and counters stay
    bit-identical to single-device runs.
  * **packed-priority eviction** — every policy's victim key is one
    uniform padded 3-tuple of int32 arrays (constant for the whole step:
    nothing an eviction changes feeds back into the keys), so victim
    selection is a chained masked-argmin over that tuple inside a
    ``while_loop`` whose body — including the ``random`` policy's PRNG
    draw — only executes on steps that actually evict, also under
    ``vmap``. (A fully vectorised sort-based "drop the ``occ - cap``
    lowest-ranked" variant was measured and rejected: batched ``cond``
    turns into ``select``, which forces the sort on every step.)
  * **Pallas victim selection** (``REPRO_SIM_KERNELS=1``, default off) —
    because the keys are constant per step, the whole multi-victim draw
    is one :mod:`repro.kernels.evict_select` kernel call: candidate mask
    + key tuple land in VMEM once and the chained masked-argmin loop runs
    in-core, instead of re-reading the state arrays per victim.  Counters
    are bit-identical to the scan path (the kernel runs the same loop;
    ``n_evict = min(occ - cap, candidates)`` and the victim SET is order
    free).  On CPU backends the kernel runs in interpret mode — same
    program as jnp ops, exercised by CI; compiled-path numbers are a
    TPU/GPU follow-up (BENCH_sim.json marks them pending).
  * **traced cell parameters** — policy, prefetcher, capacity, and the
    valid-block count are runtime values (not Python branches), so one
    compiled scan per (batch, n_blocks, events) shape bucket serves every
    benchmark x policy x prefetch x oversubscription cell, and
    :func:`run_batch` ``vmap``s whole sweeps through it in a single scan.
"""
from __future__ import annotations

import functools
import os
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.distributed.compat import lane_shardings
from repro.util import pow2_bucket
from repro.uvm import registry as _registry
from repro.uvm.registry import POLICY_IDS, PREFETCH_IDS
from repro.uvm.trace import PAGES_PER_BLOCK, Trace

CHUNK_BLOCKS = 32  # 2MB chunk = 32 x 64KB blocks
INTERVAL = 64  # page-set-chain interval, in faults (same as HPE)
NO_USE = np.int32(2**31 - 1)

# The BUILTIN strategy set (the paper's matrix). The LIVE set — builtins
# plus anything added via repro.uvm.api.register_policy/register_prefetcher
# — is registry.policy_names()/prefetcher_names(); POLICY_IDS/PREFETCH_IDS
# (imported from the registry) always reflect it.
POLICIES = ("lru", "random", "belady", "hpe", "learned")
PREFETCHERS = ("demand", "tree", "none")


class SimState(NamedTuple):
    resident: jax.Array  # bool (NB,)
    pinned: jax.Array  # bool (NB,) zero-copy blocks (never migrated)
    evicted_once: jax.Array  # bool (NB,)
    last_access: jax.Array  # int32 (NB,)
    last_interval: jax.Array  # int32 (NB,)
    next_use: jax.Array  # int32 (NB,)
    freq: jax.Array  # int32 (NB,) prediction frequency (-1 = never predicted)
    occupancy: jax.Array  # int32
    fault_count: jax.Array  # int32
    thrash_events: jax.Array  # int32 (block-granular)
    migrations: jax.Array  # int32 blocks migrated
    faults: jax.Array  # int32 far-fault events
    zero_copy: jax.Array  # int32 remote accesses to pinned blocks
    time: jax.Array  # int32
    key: jax.Array


def init_state(n_blocks: int, seed: int = 0) -> SimState:
    z = jnp.zeros((), jnp.int32)
    return SimState(
        resident=jnp.zeros(n_blocks, bool),
        pinned=jnp.zeros(n_blocks, bool),
        evicted_once=jnp.zeros(n_blocks, bool),
        last_access=jnp.full(n_blocks, -1, jnp.int32),
        last_interval=jnp.full(n_blocks, -1, jnp.int32),
        next_use=jnp.full(n_blocks, NO_USE, jnp.int32),
        freq=jnp.full(n_blocks, -1, jnp.int32),
        occupancy=z,
        fault_count=z,
        thrash_events=z,
        migrations=z,
        faults=z,
        zero_copy=z,
        time=z,
        key=jax.random.key(seed),
    )


def _ensure_key(state: SimState) -> SimState:
    """Re-wrap ``key`` if it round-tripped through :func:`jax.random.key_data`.

    ``run()`` returns the state with the key flattened to raw ``uint32`` data
    (numpy-safe); feeding that state back in (the documented resume path)
    must restore the typed PRNG key or ``random`` eviction breaks.
    """
    key = jnp.asarray(state.key)
    if not jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.wrap_key_data(key)
    return state._replace(key=key)


def precompute_next_use(blocks: np.ndarray, n_blocks: int) -> np.ndarray:
    """next_use[t] = index of the next access to blocks[t] after t (else INF)."""
    b = np.asarray(blocks, np.int64)
    nxt = np.full(len(b), NO_USE, np.int64)
    if len(b):
        idx = np.arange(len(b))
        perm = np.lexsort((idx, b))  # positions grouped by block, time ascending
        same = b[perm][1:] == b[perm][:-1]
        nxt[perm[:-1][same]] = perm[1:][same]
    return np.minimum(nxt, NO_USE).astype(np.int32)


def next_use_for(trace: Trace) -> np.ndarray:
    """Per-trace cached :func:`precompute_next_use` (shared across cells)."""
    cached = getattr(trace, "_next_use_cache", None)
    if cached is None or len(cached) != len(trace):
        cached = precompute_next_use(trace.block.astype(np.int32), trace.n_blocks)
        trace._next_use_cache = cached
    return cached


class Events(NamedTuple):
    """Compressed access stream (host side).

    One event covers ``rl`` accesses to block ``blk`` at segment offsets
    ``dt, dt + stride, ..., dt + (rl-1)*stride`` (``rl`` = 0 marks a padding
    no-op event).  ``nxt`` is the next-use index of the event's LAST covered
    access — the value ``next_use[blk]`` must hold after the event; earlier
    values are only ever read for the protected block itself, so they cannot
    influence eviction.  Two compression modes produce events:

    * ``stride == 1`` — a maximal run of consecutive same-block accesses.
      The block is protected during its own step, so accesses after the
      first cannot fault; merging them is unconditionally exact.
    * ``stride == p > 1`` — one position of a period-``p`` window (the
      ``_interleave`` idiom behind streaming traces): ``p`` distinct-ish
      blocks repeated ``r`` times.  The window's first period is emitted as
      ``p`` ordinary events; each position's remaining ``r-1`` occurrences
      are merged into one aggregate event.  Aggregates are exact ONLY if no
      covered access faults — verified at runtime via the ``pfault`` scan
      output; on divergence the caller reruns with ``periodic=False``
      (see :func:`run_segment` / :func:`run_batch`).
    """

    blk: np.ndarray  # int32 (E,)
    nxt: np.ndarray  # int32 (E,)
    dt: np.ndarray  # int32 (E,)
    rl: np.ndarray  # int32 (E,)
    stride: np.ndarray  # int32 (E,) access-index gap between covered accesses
    n_access: int  # original segment length


P_MAX = 8  # largest interleave period the host-side detector looks for
MIN_REPS = 4  # shortest window worth compressing (2p events vs ~r*p raw)


def _rle_parts(b: np.ndarray, nxt: np.ndarray, lo: int, hi: int):
    """Plain run-length events for the slice ``b[lo:hi]`` (stride == 1)."""
    n = hi - lo
    seg = b[lo:hi]
    change = np.empty(n, bool)
    change[0] = True
    np.not_equal(seg[1:], seg[:-1], out=change[1:])
    starts = (lo + np.nonzero(change)[0]).astype(np.int32)
    run_len = np.diff(np.append(starts, hi)).astype(np.int32)
    ends = starts + run_len - 1
    return seg[change], nxt[ends], starts, run_len, np.ones(len(starts), np.int32)


def _periodic_windows(b: np.ndarray) -> list[tuple[int, int, int]]:
    """Detect non-overlapping fixed-period windows: ``(start, p, reps)``.

    A window matches when ``b[t] == b[t-p]`` over its whole span.  Smaller
    periods claim coverage first; a window is kept only when its 2p events
    beat the run count plain RLE would emit for the same span.
    """
    n = len(b)
    covered = np.zeros(n, bool)
    boundary = np.empty(n, bool)  # boundary[i]: run starts at i (for the RLE-win check)
    boundary[0] = True
    np.not_equal(b[1:], b[:-1], out=boundary[1:])
    run_count = np.concatenate([[0], np.cumsum(boundary)])  # runs in b[:i] = run_count[i]
    wins = []
    for p in range(2, P_MAX + 1):
        if n < MIN_REPS * p:
            break
        m = b[p:] == b[:-p]
        edges = np.flatnonzero(np.diff(np.concatenate([[False], m, [False]]).astype(np.int8)))
        for s, e_m in zip(edges[0::2], edges[1::2]):
            length = (e_m - s) + p  # accesses b[s : s+length] are period-p
            if covered[s : s + length].any():
                bad = np.flatnonzero(covered[s : s + length])
                length = int(bad[0])
            r = length // p
            if r < MIN_REPS:
                continue
            length = r * p
            # worth it only if RLE would emit more than our 2p events
            if run_count[s + length] - run_count[s] <= 2 * p:
                continue
            covered[s : s + length] = True
            wins.append((int(s), p, r))
    wins.sort()
    return wins


def compress_events(blocks: np.ndarray, next_use: np.ndarray, *, periodic: bool = False) -> Events:
    b = np.asarray(blocks, np.int32)
    nxt_arr = np.asarray(next_use, np.int32)
    n = len(b)
    if n == 0:
        e = np.zeros(0, np.int32)
        return Events(e, e, e, e, e, 0)
    wins = _periodic_windows(b) if periodic else []
    if not wins:
        return Events(*_rle_parts(b, nxt_arr, 0, n), n)
    parts = []
    pos = 0
    for s, p, r in wins:
        if pos < s:
            parts.append(_rle_parts(b, nxt_arr, pos, s))
        j = np.arange(p, dtype=np.int32)
        ones = np.ones(p, np.int32)
        # first period: ordinary events (these may fault and evict)
        parts.append((b[s + j], nxt_arr[s + j], (s + j).astype(np.int32), ones, ones))
        # aggregates: position j's occurrences 2..r, spaced p apart
        parts.append((
            b[s + j],
            nxt_arr[s + (r - 1) * p + j],  # next use after the LAST occurrence
            (s + p + j).astype(np.int32),
            np.full(p, r - 1, np.int32),
            np.full(p, p, np.int32),
        ))
        pos = s + r * p
    if pos < n:
        parts.append(_rle_parts(b, nxt_arr, pos, n))
    cat = [np.concatenate([pt[i] for pt in parts]) for i in range(5)]
    return Events(*cat, n)


_bucket_pow2 = pow2_bucket


def bucket_blocks(n_valid: int) -> int:
    """Power-of-two state size >= pad_blocks(n_valid), so different
    benchmarks share one compiled scan. Padding blocks are never valid,
    never resident, and never migrated — they are inert. The 128 floor puts
    the entire quick-scale suite in ONE compile bucket (the padded per-step
    cost is noise next to a 1-2s XLA compile per extra shape)."""
    return _bucket_pow2(pad_blocks(n_valid), 128)


def _pad_events(ev: Events) -> Events:
    """Pad the event arrays to a power-of-two length with no-op (rl=0)
    events so scan lengths fall into a few compile buckets."""
    e = len(ev.blk)
    target = _bucket_pow2(e, 1024)
    if target == e:
        return ev
    pad = target - e

    def z(a, fill=0):
        return np.concatenate([a, np.full(pad, fill, np.int32)])

    return Events(z(ev.blk), z(ev.nxt), z(ev.dt), z(ev.rl), z(ev.stride, 1), ev.n_access)


def _tree_mask(resident, blk, valid, n_blocks: int):
    """Blocks to prefetch per the tree-based neighbourhood prefetcher."""
    mask = jnp.zeros(n_blocks, bool)
    for size in (2, 4, 8, 16, CHUNK_BLOCKS):
        node = blk // size
        occ = resident.reshape(-1, size).sum(axis=1)[node]
        trigger = occ * 2 > size  # >50% of node valid
        in_node = (jnp.arange(n_blocks) // size) == node
        mask = mask | (in_node & trigger)
    return mask & valid & ~resident


def _lru_keys(state: SimState, interval_now, t_now):
    return (state.last_access,)


def _random_keys(state: SimState, interval_now, t_now):
    r = jax.random.randint(
        jax.random.fold_in(state.key, t_now), state.last_access.shape, 0, 1 << 30, jnp.int32
    )
    return (r,)


def _belady_keys(state: SimState, interval_now, t_now):
    return (-state.next_use,)  # farthest next use evicted first


def _hpe_keys(state: SimState, interval_now, t_now):
    age = jnp.clip(interval_now - state.last_interval, 0, 2)  # 0=new..2=old
    return (-age, state.last_access)


def _learned_keys(state: SimState, interval_now, t_now):
    age = jnp.clip(interval_now - state.last_interval, 0, 2)
    return (-age, state.freq, state.last_access)


def _policy_keys(state: SimState, policy_id, interval_now, t_now, policy_fns: tuple | None = None):
    """The policy's lexicographic victim-key tuple, padded to 3 int32 keys.

    ``policy_fns`` is the registry branch table (builtins ride the same
    path a `register_policy` entry does) — passed down from the jit-cache
    key so the compiled switch always matches the table it was keyed on;
    ``None`` falls back to the live registry (direct/untraced callers).
    Extra constant keys never change a lexicographic argmin, so every
    policy shares one (k1, k2, k3) shape and one sort."""
    z = jnp.zeros_like(state.last_access)

    def pad(fn):
        def branch():
            ks = tuple(fn(state, interval_now, t_now))
            if not 1 <= len(ks) <= 3:
                raise ValueError(f"policy key_fn must return 1-3 keys, got {len(ks)}")
            ks = tuple(jnp.asarray(k, jnp.int32) for k in ks)
            return ks + (z,) * (3 - len(ks))

        return branch

    fns = policy_fns if policy_fns is not None else _registry.policy_branches()
    return jax.lax.switch(policy_id, tuple(pad(fn) for fn in fns))


def _lex_argmin(cand, *keys):
    """Index of the lexicographically-smallest key tuple among candidates."""
    for k in keys:
        kk = jnp.where(cand, k, jnp.iinfo(jnp.int32).max)
        cand = cand & (kk == kk.min())
    return jnp.argmax(cand)


def sim_kernels_enabled() -> bool:
    """Default for the ``kernels=None`` arguments: REPRO_SIM_KERNELS=1 routes
    victim selection through the Pallas kernel (and the manager's freq table
    through its kernelized subclass — see :mod:`repro.uvm.manager.core`)."""
    return os.environ.get("REPRO_SIM_KERNELS", "0").lower() not in ("0", "", "false")


def _kernel_interpret() -> bool:
    """Pallas interpret mode runs the kernels as jnp ops on backends with no
    Mosaic lowering (CPU CI) — bit-identical, just not faster."""
    return jax.default_backend() == "cpu"


def _evict_fit(state: SimState, capacity, policy_id, protect, interval_now, t_now,
               policy_fns: tuple | None = None, evict_pref=None,
               kernels: bool = False, interpret: bool = False) -> SimState:
    """Evict lowest-priority resident blocks until occupancy <= capacity.

    The victim keys are constant for the whole step (an eviction changes
    neither the remaining blocks' keys nor their evictability), so each
    victim is one chained masked-argmin over the precomputed tuple. The
    loop body — including the ``random`` policy's PRNG draw — only runs on
    steps that actually evict, which also holds under ``vmap`` (a batched
    ``while_loop`` skips the body once every lane's condition is false).

    ``evict_pref`` (optional int32 per-block array, constant for the step
    like every other key) is the QoS budget tier: it is prepended as the
    LEADING lexicographic key, so lower-preference blocks (an over-budget
    tenant's) are exhausted before ANY higher-preference block is
    considered, whatever the policy's own keys say.  ``None`` (the
    default) traces the exact pre-QoS program — bit-identical counters.

    ``kernels=True`` (a Python-static flag, part of the jit-cache key)
    replaces the while_loop with ONE :mod:`repro.kernels.evict_select`
    call selecting all ``min(max(occ - capacity, 0), |candidates|)``
    victims in-core.  Bit-identical because the keys are constant for the
    step (the ``random`` policy's draw is a pure ``fold_in`` — computing
    it once for n victims equals computing it n times) and the resulting
    resident/evicted_once/occupancy updates are victim-order free."""
    base = ~state.pinned & ~protect

    if kernels:
        from repro.kernels.evict_select import ops as _evict_ops

        cand = state.resident & base
        k1, k2, k3 = _policy_keys(state, policy_id, interval_now, t_now, policy_fns)
        keys = (k1, k2, k3) if evict_pref is None else (evict_pref, k1, k2, k3)
        n_evict = jnp.minimum(
            jnp.maximum(state.occupancy - capacity, 0), cand.sum(dtype=jnp.int32)
        )
        vict = _evict_ops.evict_select(cand, keys, n_evict, use_kernel=True, interpret=interpret)
        return state._replace(
            resident=state.resident & ~vict,
            evicted_once=state.evicted_once | vict,
            occupancy=state.occupancy - vict.sum(dtype=jnp.int32),
        )

    def cond(c):
        resident, evicted_once, occ = c
        return (occ > capacity) & ((resident & base).any())

    def body(c):
        resident, evicted_once, occ = c
        k1, k2, k3 = _policy_keys(state, policy_id, interval_now, t_now, policy_fns)
        keys = (k1, k2, k3) if evict_pref is None else (evict_pref, k1, k2, k3)
        victim = _lex_argmin(resident & base, *keys)
        return resident.at[victim].set(False), evicted_once.at[victim].set(True), occ - 1

    resident, evicted_once, occ = jax.lax.while_loop(
        cond, body, (state.resident, state.evicted_once, state.occupancy)
    )
    return state._replace(resident=resident, evicted_once=evicted_once, occupancy=occ)


def _scan_events(state: SimState, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                 policy_fns: tuple | None = None, prefetch_fns: tuple | None = None,
                 evict_pref=None, kernels: bool = False, interpret: bool = False):
    """One lane: scan the compressed event stream. All cell parameters are
    traced values — a single compile serves every (policy, prefetch,
    capacity, n_valid) combination of this shape. ``policy_fns`` /
    ``prefetch_fns`` are the registry branch tables the caller keyed its
    jit cache on (``None`` reads the live registry); ``evict_pref`` is the
    optional QoS leading victim key, constant for the whole segment (see
    :func:`_evict_fit`)."""
    n_blocks = state.resident.shape[0]
    iota = jnp.arange(n_blocks, dtype=jnp.int32)
    valid = iota < n_valid
    t0 = state.time

    def step(state: SimState, inp):
        b, nx, d, r, sd = inp
        active = r > 0
        t_first = t0 + d
        t_last = t_first + (r - 1) * sd
        is_pinned = state.pinned[b]
        fault = (~state.resident[b]) & (~is_pinned) & active

        # demand block migrates on fault; the registered prefetcher's mask
        # rides along (branch 0 — demand — migrates nothing extra)
        mig = jnp.zeros(n_blocks, bool).at[b].set(fault)
        resident1 = state.resident | mig
        zeros = lambda: jnp.zeros(n_blocks, bool)
        pf_fns = prefetch_fns if prefetch_fns is not None else _registry.prefetch_branches()
        branches = tuple(
            zeros if fn is None else (lambda fn=fn: fn(resident1, b, valid, n_blocks))
            for fn in pf_fns
        )
        pf = jax.lax.cond(fault, lambda: jax.lax.switch(prefetch_id, branches), zeros)
        mig = mig | pf
        newly = mig & ~state.resident
        n_new = newly.sum(dtype=jnp.int32)
        thrash = (newly & state.evicted_once).sum(dtype=jnp.int32)

        fault_i = fault.astype(jnp.int32)
        interval_now = state.fault_count // INTERVAL
        fc_after = state.fault_count + fault_i
        is_blk = (iota == b) & active

        # prefetched blocks count as freshly used by the DRIVER's LRU
        # (CUDA treats migrated pages as recently touched — otherwise LRU
        # instantly re-evicts them and the prefetcher ping-pongs); the
        # accessed block itself ends the run at its LAST touch.
        la = jnp.where(newly, t_first, state.last_access)
        la = jnp.where(is_blk, t_last, la)
        # ...but HPE's page-set chain only sees DEMAND touches: its
        # counters are not updated by prefetches (Section III-B — this is
        # precisely why Tree.+HPE collapses in Table II). The paper's own
        # engine ("learned") updates the chain with both (Section IV-D).
        li = jnp.where(jnp.where(policy_id == 4, newly, jnp.zeros_like(newly)), interval_now, state.last_interval)
        # repeat touches after a fault that crosses an interval boundary
        # land in the NEXT interval (the reference updates per access)
        li = jnp.where(is_blk, jnp.where(r > 1, fc_after // INTERVAL, interval_now), li)

        state2 = state._replace(
            resident=state.resident | newly,
            occupancy=state.occupancy + n_new,
            fault_count=fc_after,
            thrash_events=state.thrash_events + thrash,
            migrations=state.migrations + n_new,
            faults=state.faults + fault_i,
            zero_copy=state.zero_copy + is_pinned.astype(jnp.int32) * r,
            last_access=la,
            last_interval=li,
            next_use=jnp.where(is_blk, nx, state.next_use),
        )
        protect = jnp.zeros(n_blocks, bool).at[b].set(active)
        # padding events must not evict even if a caller handed us an
        # over-capacity state, so they see capacity == occupancy
        cap_eff = jnp.where(active, capacity, state2.occupancy)
        state3 = _evict_fit(state2, cap_eff, policy_id, protect, interval_now, t_first, policy_fns,
                            evict_pref, kernels, interpret)
        out = {
            "fault": fault,
            "thrash": thrash,
            "was_evicted": state.evicted_once[b],
            # a faulting periodic aggregate breaks the no-fault merge
            # assumption: the caller must rerun with plain RLE events
            "pfault": fault & (sd > 1),
        }
        return state3._replace(time=jnp.where(active, t_last + 1, state.time)), out

    return jax.lax.scan(step, state, (blk, nxt, dt, rl, stride))


@functools.lru_cache(maxsize=None)
def _jits_for(policy_fns: tuple, prefetch_fns: tuple, kernels: bool = False,
              interpret: bool = False):
    """The simulator's jitted entry points, keyed on the registry's branch
    tables (the ordered tuples of key/mask builder functions) plus the
    Pallas-kernel selection flags — the kernel and scan paths are distinct
    traced programs, so they get distinct compile caches.

    ``lax.switch`` clamps out-of-range indices, so a scan compiled under
    one table would silently run the wrong strategy for an id added later.
    The key tuples are CLOSED OVER by the traced scans (never re-read from
    the live registry), so key and compiled switch cannot disagree; keying
    on the table contents forces a fresh trace whenever the tables change
    AND re-hits the original compile when a ``registry.scoped()`` block
    restores them (the cache keys keep the builder functions alive, so
    identity can never be recycled onto a different function)."""

    def scan(st, blk, nxt, dt, rl, stride, cap, pol, pf, nv, ep=None):
        # the cache-key tables are CLOSED OVER here, so the compiled switch
        # can never disagree with the key (a concurrent registration between
        # key computation and tracing would otherwise alias)
        return _scan_events(st, blk, nxt, dt, rl, stride, cap, pol, pf, nv, policy_fns, prefetch_fns, ep,
                            kernels, interpret)

    # ``evict_pref=None`` is an empty pytree to jit, so the budget-free call
    # traces the EXACT pre-QoS program (not a zeros-keyed variant) — the
    # goldens pin that path bit for bit, and budget-free runs pay nothing.
    @jax.jit
    def run_events(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                   evict_pref=None):
        if evict_pref is None:
            return jax.vmap(
                lambda st, cap, pol, pf, nv: scan(st, blk, nxt, dt, rl, stride, cap, pol, pf, nv)
            )(states, capacity, policy_id, prefetch_id, n_valid)
        return jax.vmap(
            lambda st, cap, pol, pf, nv, ep: scan(st, blk, nxt, dt, rl, stride, cap, pol, pf, nv, ep)
        )(states, capacity, policy_id, prefetch_id, n_valid, evict_pref)

    @jax.jit
    def run_events_lanes(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                         evict_pref=None):
        if evict_pref is None:
            return jax.vmap(scan)(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid)
        return jax.vmap(scan)(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                              evict_pref)

    @jax.jit
    def apply_prefetch(state, mask, capacity, policy_id, evict_pref=None):
        newly = mask & ~state.resident & ~state.pinned
        n_new = newly.sum(dtype=jnp.int32)
        thrash = (newly & state.evicted_once).sum(dtype=jnp.int32)
        interval_now = state.fault_count // INTERVAL
        st = state._replace(
            resident=state.resident | newly,
            occupancy=state.occupancy + n_new,
            thrash_events=state.thrash_events + thrash,
            migrations=state.migrations + n_new,
            last_interval=jnp.where(newly, interval_now, state.last_interval),
            last_access=jnp.where(newly, state.time, state.last_access),
        )
        return _evict_fit(st, capacity, policy_id, jnp.zeros_like(newly), interval_now, state.time, policy_fns,
                          evict_pref, kernels, interpret)

    return run_events, run_events_lanes, apply_prefetch


def _use_kernels(kernels: bool | None) -> bool:
    """``kernels=None`` reads :func:`sim_kernels_enabled` (the env default);
    an explicit bool pins the path regardless of environment."""
    return sim_kernels_enabled() if kernels is None else bool(kernels)


def _jits(kernels: bool | None = None):
    """Resolve the jit triple for the requested eviction path.  Interpret
    mode is auto-selected per backend — callers never choose it."""
    k = _use_kernels(kernels)
    return _jits_for(_registry.policy_branches(), _registry.prefetch_branches(),
                     k, _kernel_interpret() if k else False)


def _run_events(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                evict_pref=None, kernels: bool | None = None):
    """Batched event scan: ``states`` and the cell parameters carry a
    leading lane axis; the event stream is shared across lanes."""
    return _jits(kernels)[0](states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                             evict_pref)


def _stack_states(states: list[SimState]) -> SimState:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *states)


@obs.spanned("simulator.unstage")
def _lane(tree, i):
    """Lane ``i`` of a lane-stacked tree (one small device slice per leaf)."""
    return jax.tree.map(lambda x: x[i], tree)


_INERT = ("lru", "demand")  # padding lane: huge capacity, cheapest policy


def _shard_lanes(stacked: SimState, lane_arrays: tuple, rep_arrays: tuple, b_pad: int,
                 kernels: bool | None = None):
    """Commit lane-stacked inputs to a cross-device lanes sharding.

    Lanes are fully independent, so GSPMD partitions the whole vmapped scan
    with no communication (the batched ``while_loop`` condition is the only
    cross-lane reduction).  No-ops on a single device, on an indivisible
    lane/device ratio, or with REPRO_SIM_SHARD=0 (checked inside
    :func:`lane_shardings`); any device_put failure (e.g. typed PRNG keys
    on an odd backend) falls back to unsharded execution — results are
    bit-identical either way, lanes just stop overlapping across devices.
    The compiled kernel path stays on one device too: GSPMD cannot
    partition a Mosaic kernel (interpret mode, plain jnp ops, can be)."""
    lane_sh, rep_sh = lane_shardings(b_pad)
    if lane_sh is None or (_use_kernels(kernels) and not _kernel_interpret()):
        return stacked, lane_arrays, rep_arrays
    try:
        st = jax.tree.map(lambda x: jax.device_put(x, lane_sh), stacked)
        la = tuple(jax.device_put(x, lane_sh) for x in lane_arrays)
        ra = tuple(jax.device_put(x, rep_sh) for x in rep_arrays)
        return st, la, ra
    except Exception:
        return stacked, lane_arrays, rep_arrays


def _run_cells(
    states: list[SimState],
    ev: Events,
    cells: list[tuple[int, int, int]],  # (policy_id, prefetch_id, capacity)
    n_valid: int,
    evict_prefs: list | None = None,
    kernels: bool | None = None,
):
    """Run one compressed stream under many cells in a single vmapped scan.

    Lanes are padded to a power of two with inert no-evict lanes so batch
    sizes fall into a few compile buckets; when several devices are
    visible, lanes are sharded across them (see :func:`_shard_lanes`).
    ``evict_prefs`` (optional, one per cell, ``None`` entries = no budget)
    stacks into the per-lane QoS leading victim key; padding lanes and
    ``None`` entries ride as all-zero rows.  That fill is safe even for
    controllers emitting NEGATIVE prefs: a ``None`` lane's row is uniform
    (a constant leading key never changes an argmin), and within a real
    lane only the tail BEYOND ``len(pref)`` is zero-filled — those are
    padding blocks, which are never resident and so never candidates
    (tests/test_properties.py::test_evict_pref_padding_invariant pins
    this against mixed negative/``None``-interleaved lanes)."""
    with obs.span("simulator.stage"):
        n_blocks = states[0].resident.shape[0]
        b_real = len(cells)
        # lane buckets {1, 8, 16, ...}: single runs stay cheap, sweeps share compiles
        b_pad = 1 if b_real == 1 else _bucket_pow2(b_real, 8)
        cells = list(cells) + [(POLICY_IDS[_INERT[0]], PREFETCH_IDS[_INERT[1]], n_blocks + 1)] * (b_pad - b_real)
        states = states + [init_state(n_blocks)] * (b_pad - b_real)
        ev = _pad_events(ev)
        pol = jnp.asarray(np.array([c[0] for c in cells], np.int32))
        pf = jnp.asarray(np.array([c[1] for c in cells], np.int32))
        cap = jnp.asarray(np.array([c[2] for c in cells], np.int32))
        nv = jnp.full(b_pad, n_valid, jnp.int32)
        ep = None
        if evict_prefs is not None and any(p is not None for p in evict_prefs):
            ep = np.zeros((b_pad, n_blocks), np.int32)
            for i, p in enumerate(evict_prefs):
                if p is not None:
                    ep[i, : len(p)] = np.asarray(p, np.int32)
            ep = jnp.asarray(ep)
        evs = tuple(jnp.asarray(getattr(ev, f)) for f in ("blk", "nxt", "dt", "rl", "stride"))
        if ep is None:
            stacked, (cap, pol, pf, nv), evs = _shard_lanes(
                _stack_states(states), (cap, pol, pf, nv), evs, b_pad, kernels)
        else:
            stacked, (cap, pol, pf, nv, ep), evs = _shard_lanes(
                _stack_states(states), (cap, pol, pf, nv, ep), evs, b_pad, kernels)
    with obs.span("simulator.dispatch"):
        out_states, outs = _run_events(stacked, *evs, cap, pol, pf, nv, ep, kernels)
    return out_states, outs, b_real


def _decompress_outs(outs_lane: dict, ev: Events) -> dict:
    """Expand per-event scan outputs back to per-access arrays.

    Periodic aggregates cover interleaved (non-contiguous) access indices,
    so per-access values are scattered to ``dt + k*stride`` rather than
    repeated contiguously."""
    e = len(ev.blk)
    ev_fault = obs.to_host(outs_lane["fault"], "simulator.outs")[:e]
    ev_thrash = obs.to_host(outs_lane["thrash"], "simulator.outs")[:e]
    ev_we = obs.to_host(outs_lane["was_evicted"], "simulator.outs")[:e]
    with obs.span("simulator.decompress"):
        fault = np.zeros(ev.n_access, bool)
        thrash = np.zeros(ev.n_access, np.int32)
        fault[ev.dt] = ev_fault
        thrash[ev.dt] = ev_thrash
        was_evicted = np.zeros(ev.n_access, bool)
        intra = np.arange(int(ev.rl.sum())) - np.repeat(np.cumsum(ev.rl) - ev.rl, ev.rl)
        pos = np.repeat(ev.dt, ev.rl) + intra * np.repeat(ev.stride, ev.rl)
        was_evicted[pos] = np.repeat(ev_we, ev.rl)
    return {"fault": fault, "thrash": thrash, "was_evicted": was_evicted}


@obs.spanned("simulator.run_segment")
def run_segment(
    state: SimState,
    blocks: np.ndarray,
    next_use: np.ndarray,
    *,
    capacity: int,
    policy: str,
    prefetch: str,
    n_valid: int,
    want_outs: bool = True,
    evict_pref: np.ndarray | None = None,
    kernels: bool | None = None,
):
    """Run one trace segment (compress -> batched scan -> decompress).

    Period-p compression is attempted first; if any periodic aggregate
    faulted (its merged occurrences are then not provably fault-free), the
    segment is rerun with plain run-length events — so the returned
    counters are always bit-identical to the per-access reference.

    ``evict_pref`` (optional int32 per-block array) is the QoS budget
    tier prepended as the LEADING victim key for the whole segment —
    lower values evict first (see :func:`_evict_fit`); budgets are
    per-segment constants, recomputed by the caller between segments.

    ``kernels`` selects the Pallas victim-selection path (``None`` =
    the ``REPRO_SIM_KERNELS`` env default) — counters are bit-identical
    either way (see :func:`_evict_fit`).
    """
    with obs.span("simulator.stage"):
        state = _ensure_key(state)
    blocks = np.asarray(blocks)
    next_use = np.asarray(next_use)
    cell = (POLICY_IDS[policy], PREFETCH_IDS[prefetch], int(capacity))

    def scan(periodic: bool):
        """One pass; ``None`` where the periodic aggregates diverged."""
        with obs.span("simulator.compress"):
            ev = compress_events(blocks, next_use, periodic=periodic)
        if ev.n_access == 0:
            z = np.zeros(0)
            return state, {"fault": z.astype(bool), "thrash": z.astype(np.int32), "was_evicted": z.astype(bool)}
        out_states, outs, _ = _run_cells([state], ev, [cell], n_valid,
                                         None if evict_pref is None else [evict_pref], kernels)
        lane = _lane(outs, 0)
        if periodic and (ev.stride > 1).any() and obs.to_host(lane["pfault"], "simulator.pfault").any():
            return None  # divergence: a merged occurrence may have faulted
        return _lane(out_states, 0), (_decompress_outs(lane, ev) if want_outs else None)

    out = scan(periodic=True)
    if out is None:
        with obs.span("simulator.rerun"):
            out = scan(periodic=False)
    return out


def _run_segment(state, blocks, next_use, n_blocks=None, capacity=None, policy=None, prefetch=None, n_valid=None, want_outs=True):
    """Back-compat wrapper with the pre-refactor keyword signature."""
    return run_segment(
        state, np.asarray(blocks), np.asarray(next_use),
        capacity=capacity, policy=policy, prefetch=prefetch, n_valid=n_valid, want_outs=want_outs,
    )


class SimResult(NamedTuple):
    state: SimState
    fault: np.ndarray
    thrash: np.ndarray
    was_evicted: np.ndarray

    @property
    def pages_thrashed(self) -> int:
        return int(self.state.thrash_events) * PAGES_PER_BLOCK

    @property
    def stats(self) -> dict:
        s = self.state
        return {
            "pages_thrashed": self.pages_thrashed,
            "faults": int(s.faults),
            "migrated_blocks": int(s.migrations),
            "zero_copy": int(s.zero_copy),
            "occupancy": int(s.occupancy),
        }


def capacity_for(n_blocks: int, oversubscription: float) -> int:
    """125% oversubscription => device memory = working set / 1.25."""
    return max(int(np.floor(n_blocks / oversubscription)), 1)


def pad_blocks(n_valid: int) -> int:
    return int(np.ceil(n_valid / CHUNK_BLOCKS) * CHUNK_BLOCKS)


def run(
    trace: Trace,
    *,
    policy: str = "lru",
    prefetch: str = "tree",
    oversubscription: float = 1.25,
    state: SimState | None = None,
    seed: int = 0,
    kernels: bool | None = None,
) -> SimResult:
    """Run a full trace under (policy x prefetch) at an oversubscription level."""
    assert policy in POLICY_IDS and prefetch in PREFETCH_IDS, (policy, prefetch)
    blocks = trace.block.astype(np.int32)
    cap = capacity_for(trace.n_blocks, oversubscription)
    nxt = next_use_for(trace)
    if state is not None:
        st = _ensure_key(jax.tree.map(jnp.asarray, state))
    else:
        st = init_state(bucket_blocks(trace.n_blocks), seed)
    st, outs = run_segment(
        st, blocks, nxt,
        capacity=cap, policy=policy,
        prefetch=prefetch,  # "none" aliases demand's id in the registry
        n_valid=trace.n_blocks,
        kernels=kernels,
    )
    st = st._replace(key=jax.random.key_data(st.key))  # numpy-safe
    return SimResult(
        state=jax.tree.map(np.asarray, st),
        fault=outs["fault"],
        thrash=outs["thrash"],
        was_evicted=outs["was_evicted"],
    )


@obs.spanned("simulator.run_batch")
def run_batch(
    trace: Trace,
    cells: list[tuple[str, str, float]],
    *,
    seed: int = 0,
    seeds: list[int] | None = None,
    kernels: bool | None = None,
) -> list[dict]:
    """Sweep many (policy, prefetch, oversubscription) cells over one trace
    in a single vmapped scan; returns one stats dict per cell, bit-identical
    (for non-``random`` policies) to running each cell through :func:`run`.
    """
    blocks = trace.block.astype(np.int32)
    nb = bucket_blocks(trace.n_blocks)
    nxt = next_use_for(trace)
    id_cells = []
    for policy, prefetch, oversub in cells:
        assert policy in POLICY_IDS and prefetch in PREFETCH_IDS, (policy, prefetch)
        id_cells.append((
            POLICY_IDS[policy],  # "none" aliases demand's id in the registry
            PREFETCH_IDS[prefetch],
            capacity_for(trace.n_blocks, oversub),
        ))
    lane_seeds = seeds if seeds is not None else [seed] * len(cells)
    with obs.span("simulator.stage"):
        states = [init_state(nb, s) for s in lane_seeds]
    with obs.span("simulator.compress"):
        ev = compress_events(blocks, nxt, periodic=True)
    out_states, outs, b_real = _run_cells(states, ev, id_cells, trace.n_blocks, kernels=kernels)
    if (ev.stride > 1).any() and bool(obs.to_host(jnp.any(outs["pfault"]), "simulator.pfault")):
        with obs.span("simulator.rerun"):  # some lane's periodic merge diverged: rerun all on RLE
            with obs.span("simulator.compress"):
                ev = compress_events(blocks, nxt, periodic=False)
            out_states, outs, b_real = _run_cells(states, ev, id_cells, trace.n_blocks, kernels=kernels)
    # one host sync for the whole sweep
    counters = obs.to_host({
        "thrash_events": out_states.thrash_events,
        "faults": out_states.faults,
        "migrations": out_states.migrations,
        "zero_copy": out_states.zero_copy,
        "occupancy": out_states.occupancy,
    }, "simulator.counters")
    return [
        {
            "pages_thrashed": int(counters["thrash_events"][i]) * PAGES_PER_BLOCK,
            "faults": int(counters["faults"][i]),
            "migrated_blocks": int(counters["migrations"][i]),
            "zero_copy": int(counters["zero_copy"][i]),
            "occupancy": int(counters["occupancy"][i]),
        }
        for i in range(b_real)
    ]


def _run_events_lanes(states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                      evict_pref=None, kernels: bool | None = None):
    """Batched event scan where EVERY input carries a leading lane axis —
    unlike :func:`_run_events`, each lane walks its OWN event stream (the
    cross-benchmark case: different traces, same shape bucket)."""
    return _jits(kernels)[1](states, blk, nxt, dt, rl, stride, capacity, policy_id, prefetch_id, n_valid,
                             evict_pref)


def run_segments_many(
    states: list[SimState],
    segments: list[tuple[np.ndarray, np.ndarray]],  # (blocks, next_use) per lane
    cells: list[tuple[int, int, int]],  # (policy_id, prefetch_id, capacity) per lane
    n_valids: list[int],
    *,
    want_outs: bool = True,
    evict_prefs: list | None = None,
    kernels: bool | None = None,
) -> list[tuple[SimState, dict | None]]:
    """Run one trace segment per lane in bucketed vmapped scans.

    Lanes are grouped by (state width, padded event length); each group runs
    as ONE vmapped scan over stacked per-lane event streams (short lanes are
    padded with no-op events).  Lanes whose periodic aggregates diverged are
    rerun individually on plain RLE events, so every lane's counters stay
    bit-identical to the reference regardless of batching.

    ``evict_prefs`` (optional, one entry per lane, ``None`` = no budget)
    carries each lane's QoS leading victim key (see :func:`run_segment`);
    ``kernels`` selects the Pallas victim-selection path for every lane
    (``None`` = the ``REPRO_SIM_KERNELS`` env default).
    """
    results: list = [None] * len(states)
    eps = evict_prefs if evict_prefs is not None else [None] * len(states)
    groups: dict = {}
    for i, (st, (blocks, next_use)) in enumerate(zip(states, segments)):
        with obs.span("simulator.stage"):
            st = _ensure_key(st)
        with obs.span("simulator.compress"):
            ev = compress_events(np.asarray(blocks), np.asarray(next_use), periodic=True)
        if ev.n_access == 0:
            z = np.zeros(0)
            results[i] = (st, {"fault": z.astype(bool), "thrash": z.astype(np.int32), "was_evicted": z.astype(bool)})
            continue
        padded = _pad_events(ev)
        key = (st.resident.shape[0], len(padded.blk))
        # decompression must see the UNPADDED events (padding rows carry
        # dt=0 and would scatter junk over the first access's outputs)
        groups.setdefault(key, []).append((i, st, ev, padded))

    def _rle_rerun(i, st):
        """Exact single-lane rerun on plain RLE events (shares the b_pad=1
        compile bucket with run/run_segment)."""
        with obs.span("simulator.rerun"):
            with obs.span("simulator.compress"):
                ev_r = compress_events(np.asarray(segments[i][0]), np.asarray(segments[i][1]))
            o_st, o_outs, _ = _run_cells([st], ev_r, [cells[i]], n_valids[i],
                                         None if eps[i] is None else [eps[i]], kernels)
            return _lane(o_st, 0), (_decompress_outs(_lane(o_outs, 0), ev_r) if want_outs else None)

    for (nb, e_len), lanes in groups.items():
        if len(lanes) < 4:
            # small groups route through the single-lane path: reuses the
            # compiled shapes every serial caller already has, instead of
            # minting one vmapped compile per odd lane count
            for i, st, ev, _ in lanes:
                out_states, outs, _ = _run_cells([st], ev, [cells[i]], n_valids[i],
                                                 None if eps[i] is None else [eps[i]], kernels)
                lane = _lane(outs, 0)
                if (ev.stride > 1).any() and obs.to_host(lane["pfault"], "simulator.pfault").any():
                    results[i] = _rle_rerun(i, st)
                else:
                    results[i] = (_lane(out_states, 0), _decompress_outs(lane, ev) if want_outs else None)
            continue
        # lane counts fall into power-of-two buckets (inert padding lanes:
        # empty no-op event streams, never migrate) so every round of a
        # sweep reuses one compiled scan per bucket
        with obs.span("simulator.stage"):
            b_real = len(lanes)
            b_pad = _bucket_pow2(b_real, 4)
            idxs = [i for i, *_ in lanes]
            pad_ev = Events(*(np.zeros(e_len, np.int32),) * 5, 0)
            stacked = _stack_states([st for _, st, _, _ in lanes] + [init_state(nb)] * (b_pad - b_real))
            arrs = [
                jnp.asarray(np.stack([getattr(p, f) for *_, p in lanes] + [getattr(pad_ev, f)] * (b_pad - b_real)))
                for f in ("blk", "nxt", "dt", "rl", "stride")
            ]
            pad_cell = (POLICY_IDS[_INERT[0]], PREFETCH_IDS[_INERT[1]], nb + 1)
            cell_arr = [
                jnp.asarray(np.array([cells[i][k] for i in idxs] + [pad_cell[k]] * (b_pad - b_real), np.int32))
                for k in range(3)
            ]
            nv = jnp.asarray(np.array([n_valids[i] for i in idxs] + [nb] * (b_pad - b_real), np.int32))
            ep = None
            if any(eps[i] is not None for i in idxs):
                ep_np = np.zeros((b_pad, nb), np.int32)
                for j, i in enumerate(idxs):
                    if eps[i] is not None:
                        ep_np[j, : len(eps[i])] = np.asarray(eps[i], np.int32)
                ep = jnp.asarray(ep_np)
            if ep is None:
                stacked, lane_arrs, _ = _shard_lanes(stacked, (*arrs, *cell_arr, nv), (), b_pad, kernels)
                *arrs, pol_a, pf_a, cap_a, nv = lane_arrs
            else:
                stacked, lane_arrs, _ = _shard_lanes(stacked, (*arrs, *cell_arr, nv, ep), (), b_pad, kernels)
                *arrs, pol_a, pf_a, cap_a, nv, ep = lane_arrs
        with obs.span("simulator.dispatch"):
            out_states, outs = _run_events_lanes(stacked, *arrs, cap_a, pol_a, pf_a, nv, ep, kernels)
        pdiv = obs.to_host(outs["pfault"], "simulator.pfault").any(axis=1)
        for j, (i, st, ev, _) in enumerate(lanes):
            if pdiv[j]:
                results[i] = _rle_rerun(i, st)  # periodic merge diverged
            else:
                results[i] = (
                    _lane(out_states, j),
                    _decompress_outs(_lane(outs, j), ev) if want_outs else None,
                )
    return results


def _apply_prefetch_jit(state: SimState, mask, capacity, policy_id, evict_pref=None,
                        kernels: bool | None = None):
    return _jits(kernels)[2](state, mask, capacity, policy_id, evict_pref)


def apply_prefetch(state: SimState, blocks_mask, *, capacity: int, policy: str = "learned",
                   evict_pref: np.ndarray | None = None, kernels: bool | None = None) -> SimState:
    """Stage externally-predicted prefetches (the learned runtime's async
    path).  ``evict_pref`` is the optional QoS leading victim key for the
    fit-back eviction (see :func:`run_segment`); ``kernels`` selects the
    Pallas victim-selection path (``None`` = env default)."""
    state = _ensure_key(state)
    return _apply_prefetch_jit(
        state, jnp.asarray(blocks_mask),
        jnp.asarray(capacity, jnp.int32), jnp.asarray(POLICY_IDS[policy], jnp.int32),
        None if evict_pref is None else jnp.asarray(evict_pref, jnp.int32),
        kernels,
    )


# --- builtin registrations -------------------------------------------------
# The paper's strategy matrix enters the SAME registry a user plugin does;
# registration order fixes the traced ids (lru=0 .. learned=4, demand=0,
# tree=1, none->demand) that the goldens and the batch-padding _INERT lane
# rely on. Guarded for idempotence under importlib.reload.
if "lru" not in POLICY_IDS:
    _registry.register_policy("lru", _lru_keys)
    _registry.register_policy("random", _random_keys)
    _registry.register_policy("belady", _belady_keys)
    _registry.register_policy("hpe", _hpe_keys)
    _registry.register_policy("learned", _learned_keys)
    _registry.register_prefetcher("demand", None)
    _registry.register_prefetcher("tree", _tree_mask)
    _registry.register_prefetcher("none", alias_of="demand")
