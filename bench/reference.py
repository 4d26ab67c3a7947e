"""Plain references for what the timed path produces.

Nothing here imports the program.  Each reference is written from the
semantics the paper and the configuration state, in the most direct form:

* :class:`RefSim` — the trace simulator, one access per scan step, one
  victim per loop iteration, every policy's lexicographic keys spelled
  out (LRU, Belady, HPE, the learned engine's), the tree prefetcher, and
  the learned runtime's prefetch staging.  Integer state, so it agrees
  with the program bit for bit or not at all.
* :func:`forward`, :func:`train_group` — the dual-block Transformer page
  predictor (Section IV-B) and its fine-tune (Eq. 3 loss: cross-entropy,
  LUCIR distillation, thrashing term; AdamW with global-norm clipping),
  in ``jax.numpy`` at float32 with ``highest`` matmul precision, or at a
  lower precision (:data:`PRECISIONS`) for the control.
* :class:`LoopTable` — the prediction-frequency table (Section IV-D:
  1024 sets, 16 ways, 6-bit saturating counters, flushed every third
  interval), one block at a time.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

NO_USE = 2**31 - 1
INTERVAL = 64  # faults per page-set-chain interval
CHUNK = 32  # blocks in the tree prefetcher's largest node (2 MB)
POLICIES = ("lru", "belady", "hpe", "learned")
PREFETCHERS = ("demand", "tree")
LEARNED = POLICIES.index("learned")
I32_MAX = np.iinfo(np.int32).max


# --- simulator ---------------------------------------------------------------


def next_use(blocks: np.ndarray, n_blocks: int) -> np.ndarray:
    """Index of the next access to the same block, else ``NO_USE``."""
    nxt = np.full(len(blocks), NO_USE, np.int64)
    last = np.full(n_blocks, NO_USE, np.int64)
    for t in range(len(blocks) - 1, -1, -1):
        nxt[t] = last[blocks[t]]
        last[blocks[t]] = t
    return nxt.astype(np.int32)


def init_state(n_blocks: int) -> dict:
    z = jnp.zeros((), jnp.int32)
    return {
        "resident": jnp.zeros(n_blocks, bool), "pinned": jnp.zeros(n_blocks, bool),
        "evicted_once": jnp.zeros(n_blocks, bool),
        "last_access": jnp.full(n_blocks, -1, jnp.int32), "last_interval": jnp.full(n_blocks, -1, jnp.int32),
        "next_use": jnp.full(n_blocks, NO_USE, jnp.int32), "freq": jnp.full(n_blocks, -1, jnp.int32),
        "occupancy": z, "fault_count": z, "thrash_events": z, "migrations": z, "faults": z,
        "zero_copy": z, "time": z,
    }


def counters(state: dict) -> dict:
    """The counters a user reads, as the program reports them."""
    g = jax.device_get(state)
    return {"pages_thrashed": int(g["thrash_events"]) * 16, "faults": int(g["faults"]),
            "migrated_blocks": int(g["migrations"]), "zero_copy": int(g["zero_copy"]),
            "occupancy": int(g["occupancy"])}


def _victim(s: dict, policy, interval_now, cand):
    """First block of the lexicographically smallest key tuple."""
    age = jnp.clip(interval_now - s["last_interval"], 0, 2)
    zero = jnp.zeros_like(age)
    keys = [
        jnp.select([policy == 0, policy == 1], [s["last_access"], -s["next_use"]], -age),
        jnp.select([policy == 2, policy == 3], [s["last_access"], s["freq"]], zero),
        jnp.where(policy == 3, s["last_access"], zero),
    ]
    for k in keys:
        kk = jnp.where(cand, k, I32_MAX)
        cand = cand & (kk == kk.min())
    return jnp.argmax(cand)


def _evict_to_fit(s: dict, capacity, policy, protect, interval_now) -> dict:
    def cond(c):
        resident, _, occ = c
        return (occ > capacity) & (resident & ~s["pinned"] & ~protect).any()

    def body(c):
        resident, evicted, occ = c
        v = _victim({**s, "resident": resident}, policy, interval_now, resident & ~s["pinned"] & ~protect)
        return resident.at[v].set(False), evicted.at[v].set(True), occ - 1

    resident, evicted, occ = jax.lax.while_loop(cond, body, (s["resident"], s["evicted_once"], s["occupancy"]))
    return {**s, "resident": resident, "evicted_once": evicted, "occupancy": occ}


def _tree(resident, blk, valid, n_blocks: int):
    mask = jnp.zeros(n_blocks, bool)
    idx = jnp.arange(n_blocks)
    for size in (2, 4, 8, 16, CHUNK):
        node = blk // size
        filled = resident.reshape(-1, size).sum(axis=1)[node] * 2 > size
        mask = mask | ((idx // size == node) & filled)
    return mask & valid & ~resident


def _access(s: dict, blk, nxt, live, capacity, policy, prefetch, n_valid):
    """One access (``live`` False: a padding step that changes nothing)."""
    n_blocks = s["resident"].shape[0]
    idx = jnp.arange(n_blocks)
    t = s["time"]
    pinned = s["pinned"][blk]
    fault = ~s["resident"][blk] & ~pinned & live
    mig = (idx == blk) & fault
    pf = _tree(s["resident"] | mig, blk, idx < n_valid, n_blocks) & fault & (prefetch == 1)
    newly = (mig | pf) & ~s["resident"]
    n_new = newly.sum(dtype=jnp.int32)
    interval_now = s["fault_count"] // INTERVAL
    here = (idx == blk) & live
    chain = jnp.where(policy == LEARNED, newly, False) | here
    s2 = {
        **s,
        "resident": s["resident"] | newly,
        "occupancy": s["occupancy"] + n_new,
        "fault_count": s["fault_count"] + fault.astype(jnp.int32),
        "thrash_events": s["thrash_events"] + (newly & s["evicted_once"]).sum(dtype=jnp.int32),
        "migrations": s["migrations"] + n_new,
        "faults": s["faults"] + fault.astype(jnp.int32),
        "zero_copy": s["zero_copy"] + (pinned & live).astype(jnp.int32),
        "last_access": jnp.where(newly | here, t, s["last_access"]),
        "last_interval": jnp.where(chain, interval_now, s["last_interval"]),
        "next_use": jnp.where(here, nxt, s["next_use"]),
    }
    s3 = _evict_to_fit(s2, jnp.where(live, capacity, s2["occupancy"]), policy, here, interval_now)
    s3["time"] = t + live.astype(jnp.int32)
    out = (fault, (newly & s["evicted_once"]).sum(dtype=jnp.int32), s["evicted_once"][blk] & live)
    return s3, out


@jax.jit
def _segment(s, blocks, nxts, lives, capacity, policy, prefetch, n_valid):
    def step(c, x):
        return _access(c, *x, capacity, policy, prefetch, n_valid)

    return jax.lax.scan(step, s, (blocks, nxts, lives))


@jax.jit
def _stage(s, freq, mask, capacity):
    """The learned runtime's staging: export the engine's counters, then
    migrate the prefetch set and evict (learned policy) back to fit."""
    s = {**s, "freq": freq}
    newly = mask & ~s["resident"] & ~s["pinned"]
    n_new = newly.sum(dtype=jnp.int32)
    interval_now = s["fault_count"] // INTERVAL
    s = {
        **s,
        "resident": s["resident"] | newly, "occupancy": s["occupancy"] + n_new,
        "thrash_events": s["thrash_events"] + (newly & s["evicted_once"]).sum(dtype=jnp.int32),
        "migrations": s["migrations"] + n_new,
        "last_interval": jnp.where(newly, interval_now, s["last_interval"]),
        "last_access": jnp.where(newly, s["time"], s["last_access"]),
    }
    return _evict_to_fit(s, capacity, jnp.int32(LEARNED), jnp.zeros_like(newly), interval_now)


def _pad(a: np.ndarray, n: int, fill=0) -> np.ndarray:
    return np.concatenate([a, np.full(n - len(a), fill, a.dtype)]) if len(a) < n else a


class RefSim:
    """The reference simulator over one device of ``n_blocks`` blocks."""

    def __init__(self, n_blocks: int, n_valid: int, blocks: np.ndarray):
        self.n_blocks, self.n_valid = n_blocks, n_valid
        self.blocks = np.asarray(blocks, np.int32)
        self.nxt = next_use(self.blocks, n_blocks)

    def sweep(self, cells: list[tuple[int, int, int]]) -> list[dict]:
        """Every (policy id, prefetch id, capacity) lane over the whole trace."""
        n = len(self.blocks)
        lanes = [init_state(self.n_blocks) for _ in cells]
        s = jax.tree.map(lambda *xs: jnp.stack(xs), *lanes)
        pol, pf, cap = (jnp.asarray(np.array([c[k] for c in cells], np.int32)) for k in range(3))
        run = jax.vmap(_segment, in_axes=(0, None, None, None, 0, 0, 0, None))
        s, _ = run(s, jnp.asarray(self.blocks), jnp.asarray(self.nxt), jnp.ones(n, bool),
                   cap, pol, pf, jnp.int32(self.n_valid))
        return [counters(jax.tree.map(lambda x: x[i], s)) for i in range(len(cells))]

    def learned(self, rounds: list[tuple[int, int, np.ndarray | None, np.ndarray]], capacity: int, pad_to: int):
        """Replay the learned runtime: per round ``(g0, g1, counters,
        prefetch_blocks)``, stage the engine's actions (skipped when its
        gate was closed, ``counters is None``), then run the accesses
        ``g0:g1`` under the learned policy with demand migration.  Returns
        the per-round outputs and the final counters."""
        s = init_state(self.n_blocks)
        cap = jnp.int32(capacity)
        outs = []
        for g0, g1, cnt, pf_blocks in rounds:
            if cnt is not None:
                mask = np.zeros(self.n_blocks, bool)
                mask[np.asarray(pf_blocks, np.int64)] = True
                s = _stage(s, jnp.asarray(np.asarray(cnt, np.int32)), jnp.asarray(mask), cap)
            m = g1 - g0
            s, (fault, thrash, we) = _segment(
                s, jnp.asarray(_pad(self.blocks[g0:g1], pad_to)), jnp.asarray(_pad(self.nxt[g0:g1], pad_to)),
                jnp.asarray(np.arange(pad_to) < m), cap, jnp.int32(LEARNED), jnp.int32(0), jnp.int32(self.n_valid))
            fault, thrash, we = jax.device_get((fault, thrash, we))
            outs.append({"fault": fault[:m], "thrash": thrash[:m], "was_evicted": we[:m]})
        return outs, counters(s)


# --- predictor ---------------------------------------------------------------

#: a precision: the dtype of parameters and activations, and the matmul
#: precision, by name
PRECISIONS = {"highest": (jnp.float32, "highest"), "high": (jnp.float32, "high"),
              "bfloat16": (jnp.bfloat16, "default")}


def _rms(x, scale, eps=1e-5):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta=10_000.0):
    """x: (B, T, H, D), positions 0..T-1, rotating the two halves of D."""
    T, D = x.shape[1], x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, D, 2, dtype=jnp.float32) / D))
    ang = (jnp.arange(T, dtype=jnp.float32)[:, None] * freqs)[None, :, None, :]
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _block(p, pre: str, x, n_layers: int):
    """Pre-norm Transformer layers (causal attention, SwiGLU) + final norm."""
    T = x.shape[1]
    causal = jnp.tril(jnp.ones((T, T), bool))
    for i in range(n_layers):
        h = _rms(x, p[f"{pre}/norm1/scale"][i])
        q = _rope(jnp.einsum("btd,dhk->bthk", h, p[f"{pre}/attn/wq"][i]))
        k = _rope(jnp.einsum("btd,dhk->bthk", h, p[f"{pre}/attn/wk"][i]))
        v = jnp.einsum("btd,dhk->bthk", h, p[f"{pre}/attn/wv"][i])
        s = jnp.einsum("bqhk,bshk->bhqs", q, k) * (q.shape[-1] ** -0.5)
        s = jnp.where(causal, s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqs,bshk->bqhk", a, v)
        x = x + jnp.einsum("bqhk,hkd->bqd", o, p[f"{pre}/attn/wo"][i])
        h = _rms(x, p[f"{pre}/norm2/scale"][i])
        x = x + (jax.nn.silu(h @ p[f"{pre}/mlp/wg"][i]) * (h @ p[f"{pre}/mlp/wu"][i])) @ p[f"{pre}/mlp/wd"][i]
    return _rms(x, p[f"{pre}_final/scale"])


def forward(p: dict, batch: dict, n_layers: int, cosine_scale: float):
    """Logits over delta classes and the (B, d_model) feature, in the
    dtype of ``p``."""
    reg = p["embed/page"][batch["page"]] + p["embed/delta"][batch["delta"]] + p["pos"][None]
    irr = p["embed/pc"][batch["pc"]] + p["embed/tb"][batch["tb"]] + p["pos"][None]
    f = jnp.concatenate([p["gate/reg"] * _block(p, "reg", reg, n_layers)[:, -1],
                         p["gate/irr"] * _block(p, "irr", irr, n_layers)[:, -1]], axis=-1)
    f = f @ p["head/proj"]
    w = p["head/classes"]
    fn = f / (jnp.linalg.norm(f, axis=-1, keepdims=True) + 1e-8)
    wn = w / (jnp.linalg.norm(w, axis=-1, keepdims=True) + 1e-8)
    return cosine_scale * (fn @ wn.T), f


def masked(logits, n_active):
    return jnp.where(jnp.arange(logits.shape[-1]) >= n_active, -1e30, logits.astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("n_layers", "cosine_scale", "prec"))
def logits_of(p, batch, n_active, n_layers: int, cosine_scale: float, prec: str = "highest"):
    """Masked float32 logits, computed at precision ``prec``."""
    dtype, matmul = PRECISIONS[prec]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype) if jnp.issubdtype(a.dtype, jnp.floating) else a, t)
    with jax.default_matmul_precision(matmul):
        return masked(forward(cast(p), batch, n_layers, cosine_scale)[0], n_active)


def _ce(logits, labels, n_active):
    lm = masked(logits, n_active)
    return jax.nn.logsumexp(lm, -1) - jnp.take_along_axis(lm, labels[:, None], 1)[:, 0]


def loss_fn(p, prev, batch, labels, in_et, n_active, n_layers, cosine_scale, lam, mu, use_lucir, use_thrash):
    """Eq. 3: mean CE + lambda * (1 - cos(f, f_old)) + mu * (-CE over E u T)."""
    logits, f = forward(p, batch, n_layers, cosine_scale)
    nll = _ce(logits, labels, n_active)
    loss = nll.mean()
    if use_lucir:
        f_old = jax.lax.stop_gradient(forward(prev, batch, n_layers, cosine_scale)[1])
        cos = jnp.sum(f / (jnp.linalg.norm(f, axis=-1, keepdims=True) + 1e-8)
                      * f_old / (jnp.linalg.norm(f_old, axis=-1, keepdims=True) + 1e-8), -1)
        loss = loss + lam * (1.0 - cos).mean()
    if use_thrash:
        s = in_et.astype(jnp.float32)
        loss = loss + mu * (-(nll * s).sum() / jnp.maximum(s.sum(), 1.0))
    return loss.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("n_layers", "cosine_scale", "lam", "mu", "use_lucir",
                                             "use_thrash", "lr", "prec"))
def adamw_step(p, m, v, step, prev, batch, labels, in_et, n_active, *, n_layers, cosine_scale, lam, mu,
               use_lucir, use_thrash, lr, prec="highest"):
    """One AdamW step (b1 0.9, b2 0.95, eps 1e-8, weight decay 0.01,
    gradients clipped to global norm 1): the loss and its gradient in
    precision ``prec``, the optimizer's arithmetic in float32."""
    dtype, matmul = PRECISIONS[prec]
    cast = lambda t: jax.tree.map(lambda a: a.astype(dtype), t)
    with jax.default_matmul_precision(matmul):
        g = jax.grad(loss_fn)(cast(p), cast(prev), batch, labels, in_et, n_active, n_layers, cosine_scale,
                              lam, mu, use_lucir, use_thrash)
    g = jax.tree.map(lambda a: a.astype(jnp.float32), g)
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(a)) for a in jax.tree.leaves(g)))
    g = jax.tree.map(lambda a: a * jnp.minimum(1.0, 1.0 / jnp.maximum(norm, 1e-9)), g)
    t = step.astype(jnp.float32) + 1.0
    m = jax.tree.map(lambda a, b: 0.9 * a + 0.1 * b, m, g)
    v = jax.tree.map(lambda a, b: 0.95 * a + 0.05 * b * b, v, g)
    p = jax.tree.map(lambda w, a, b: w - lr * ((a / (1 - 0.9 ** t)) / (jnp.sqrt(b / (1 - 0.95 ** t)) + 1e-8)
                                                + 0.01 * w), p, m, v)
    return p, m, v


def schedule(n: int, epochs: int, batch_size: int, seed: int) -> list[np.ndarray]:
    """Each epoch a fresh permutation cut into full batches; a group
    smaller than one batch repeats itself to fill one."""
    rng = np.random.default_rng(seed)
    rows = []
    for _ in range(epochs):
        order = rng.permutation(n)
        rows += [order[lo:lo + batch_size] for lo in range(0, n - batch_size + 1, batch_size)]
        if n < batch_size:
            rows.append(np.resize(order, batch_size))
    return rows


def train_group(p, m, v, step: int, prev, feats: dict, labels, in_et, n_active: int, *, pcfg: dict,
                tcfg: dict, use_lucir: bool, prec: str = "highest", reverse_rows: bool = False):
    """The fine-tune of one group: ``epochs`` passes of AdamW steps.
    ``reverse_rows`` takes each batch's rows in reverse order: the same
    sums in exact arithmetic, rounded in another order."""
    use_thrash = in_et is not None
    et = jnp.asarray(np.zeros(len(labels), bool) if in_et is None else np.asarray(in_et, bool))
    kw = dict(n_layers=pcfg["num_layers"], cosine_scale=float(pcfg["cosine_scale"]),
              lam=float(pcfg["lucir_lambda"]), mu=float(pcfg["thrash_mu"]), use_lucir=use_lucir,
              use_thrash=use_thrash, lr=float(tcfg["lr"]), prec=prec)
    f = {k: jnp.asarray(a) for k, a in feats.items()}
    y = jnp.asarray(labels)
    for i, idx in enumerate(schedule(len(labels), tcfg["epochs"], tcfg["batch_size"], tcfg["seed"])):
        idx = jnp.asarray(idx[::-1] if reverse_rows else idx)
        p, m, v = adamw_step(p, m, v, jnp.int32(step + i), prev, {k: a[idx] for k, a in f.items()}, y[idx],
                             et[idx], jnp.int32(n_active), **kw)
    return p


@functools.partial(jax.jit, static_argnames=("n_layers", "cosine_scale", "lam", "mu", "use_lucir", "use_thrash"))
def _grad_norms(p, prev, batch, labels, in_et, n_active, *, n_layers, cosine_scale, lam, mu, use_lucir,
                use_thrash):
    with jax.default_matmul_precision("highest"):
        g = jax.grad(loss_fn)(p, prev, batch, labels, in_et, n_active, n_layers, cosine_scale, lam, mu,
                              use_lucir, use_thrash)
    return jax.tree.map(lambda a: jnp.sqrt(jnp.sum(jnp.square(a))), g)


def first_grad_norms(p, m, v, step: int, prev, feats: dict, labels, in_et, n_active: int, *, pcfg: dict,
                     tcfg: dict, use_lucir: bool) -> dict:
    """Each leaf's gradient norm on the group's first batch (float32)."""
    idx = schedule(len(labels), tcfg["epochs"], tcfg["batch_size"], tcfg["seed"])[0]
    et = np.zeros(len(labels), bool) if in_et is None else np.asarray(in_et, bool)
    out = _grad_norms(p, prev, {k: jnp.asarray(np.asarray(a)[idx]) for k, a in feats.items()},
                      jnp.asarray(np.asarray(labels)[idx]), jnp.asarray(et[idx]), jnp.int32(n_active),
                      n_layers=pcfg["num_layers"], cosine_scale=float(pcfg["cosine_scale"]),
                      lam=float(pcfg["lucir_lambda"]), mu=float(pcfg["thrash_mu"]), use_lucir=use_lucir,
                      use_thrash=in_et is not None)
    return {k: float(x) for k, x in jax.device_get(out).items()}


# --- prediction-frequency table ------------------------------------------------


class LoopTable:
    """Set-associative counters, one predicted block at a time: a hit
    counts up (saturating at 63), a miss takes the first empty way, else
    the way with the lowest counter (first on ties), starting from zero."""

    def __init__(self, n_sets: int = 1024, ways: int = 16, counter_max: int = 63, flush_every: int = 3):
        self.n_sets, self.ways, self.max, self.flush_every = n_sets, ways, counter_max, flush_every
        self.tags = np.full((n_sets, ways), -1, np.int64)
        self.counters = np.zeros((n_sets, ways), np.int64)
        self.since_flush = 0

    def update(self, blocks) -> None:
        for b in np.asarray(blocks, np.int64).ravel().tolist():
            s = b % self.n_sets
            row = self.tags[s].tolist()
            if b in row:
                w = row.index(b)
            else:
                w = row.index(-1) if -1 in row else int(np.argmin(self.counters[s]))
                self.tags[s, w] = b
                self.counters[s, w] = 0
            self.counters[s, w] = min(self.counters[s, w] + 1, self.max)

    def intervals(self, n: int) -> None:
        self.since_flush += n
        if self.since_flush >= self.flush_every:
            self.tags.fill(-1)
            self.counters.fill(0)
            self.since_flush = 0

    def dense(self, n_blocks: int) -> np.ndarray:
        out = np.full(n_blocks, -1, np.int64)
        ok = (self.tags >= 0) & (self.tags < n_blocks)
        out[self.tags[ok]] = self.counters[ok]
        return out
