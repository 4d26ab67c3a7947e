"""Packed-priority victim-selection Pallas kernel.

The simulator's eviction hot path re-reads four (NB,) key arrays from the
traced scan state on every victim draw; this kernel loads the candidate
mask and the full lexicographic key tuple into VMEM ONCE and walks the
whole multi-victim selection in-core — one kernel invocation per scan
step instead of one masked-argmin sweep per victim (the GPUVM bet:
management-loop state stays device-resident).

Bit-identity contract: the victim set equals the simulator's chained
masked-argmin ``while_loop`` (``_lex_argmin`` semantics — smallest
(k0, k1, k2, k3) tuple first, ties to the lowest block index), because
the keys are constant for the whole step.  The kernel is shape-generic
over NB and composes with ``vmap`` (the batching rule adds a lane grid
axis) and ``lax.scan`` — the simulator calls it inside its per-event
step.  ``interpret=True`` runs the identical program as jnp ops so CPU
CI exercises the kernel path bit-for-bit.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

I32_MAX = jnp.iinfo(jnp.int32).max


def _select_kernel(n_ref, cand_ref, k0_ref, k1_ref, k2_ref, k3_ref, vict_ref):
    """One program: select ``n_ref[0, 0]`` victims from the VMEM-resident keys.

    The (NB,) arrays arrive as a lane-dense (NB // C, C) tile (see
    :func:`_tile_shape`); ``idx`` is each slot's flat block index, so
    "ties to the lowest index" is the row-major order of the tile.  The
    victim is the first true slot, ``min(where(m, idx, NB))`` — Mosaic has
    no integer argmax.  Masks ride the loop as int32 (0/1)."""
    keys = (k0_ref[...], k1_ref[...], k2_ref[...], k3_ref[...])
    cand = cand_ref[...]
    rows, cols = cand.shape
    nb = rows * cols
    idx = (jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 0) * cols
           + jax.lax.broadcasted_iota(jnp.int32, (rows, cols), 1))

    def body(_, c):
        cand_now, vict = c
        m = cand_now != 0
        for k in keys:
            kk = jnp.where(m, k, I32_MAX)
            m = m & (kk == jnp.min(kk))
        hit = idx == jnp.min(jnp.where(m, idx, nb))
        return jnp.where(hit, 0, cand_now), jnp.where(hit, 1, vict)

    # the loop stops when candidates run out, like the simulator's ``cond``
    n = jnp.minimum(n_ref[0, 0], jnp.sum(cand))
    _, vict = jax.lax.fori_loop(0, n, body, (cand, jnp.zeros_like(cand)))
    vict_ref[...] = vict


def _tile_shape(nb: int) -> tuple[int, int]:
    """Lane-dense 2-D view of an (NB,) array: 128-wide rows when NB allows
    (every simulator block bucket does), else one row of NB."""
    cols = 128 if nb % 128 == 0 else nb
    return nb // cols, cols


def evict_select(cand, keys, n_evict, *, interpret: bool = False):
    """Victim mask (bool (NB,)): the ``n_evict`` lowest-priority candidates.

    ``keys`` is a tuple of up to 4 int32 (NB,) arrays, leading key first
    (missing keys are padded with constant zeros, which never change a
    lexicographic argmin).  ``n_evict`` is an int32 scalar, read from SMEM
    — the kernel's in-core loop also stops when candidates run out,
    mirroring the simulator's ``cond``, so an over-large ``n_evict`` cannot
    overdraw.
    """
    cand = jnp.asarray(cand)
    nb = cand.shape[0]
    keys = tuple(jnp.asarray(k, jnp.int32) for k in keys)
    if not 1 <= len(keys) <= 4:
        raise ValueError(f"evict_select takes 1-4 keys, got {len(keys)}")
    keys = keys + (jnp.zeros(nb, jnp.int32),) * (4 - len(keys))
    shape = _tile_shape(nb)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    vict = pl.pallas_call(
        _select_kernel,
        out_shape=jax.ShapeDtypeStruct(shape, jnp.int32),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)] + [vmem] * 5,
        out_specs=vmem,
        interpret=interpret,
    )(jnp.full((1, 1), n_evict, jnp.int32), cand.astype(jnp.int32).reshape(shape),
      *(k.reshape(shape) for k in keys))
    return vict.reshape(nb) != 0
