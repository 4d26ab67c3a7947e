"""Prediction-frequency-table Pallas kernels (update stream + lookup).

The host table round-trips every ``update``/``lookup_many`` batch through
numpy scatter waves; these kernels keep the whole (S, W) tag/counter state
VMEM-resident and walk the block stream in-core — the GPUVM bet applied to
the paper's 18KB table (1024 sets x 16 ways fits VMEM with room to spare).

``update`` tiles the set axis across the grid: each program owns a disjoint
row tile, streams the ENTIRE block sequence in a ``fori_loop``, and applies
only the blocks hashing into its tile — programs never write the same row,
and within a program arrival order is preserved, so the result is exactly
the per-block loop oracle (first-hit way, first-empty way, lowest-counter
eviction with first-on-ties, saturating +1).  ``lookup`` is one program
gathering per-block rows with the same first-hit-way rule.

The block stream (and the lookup's per-block output) lives in SMEM: the
kernels read and write it one scalar at a time, which Mosaic allows from
SMEM only.  SMEM holds 1 MiB on a v5e, so one call takes at most
``MAX_STREAM`` blocks; :mod:`.ops` splits longer streams.

``interpret=True`` runs the identical program as jnp ops (CPU CI gate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.policy import COUNTER_MAX

_MAX_TILE = 128  # set-rows per program: 128 x 16 x int32 = 8KB per operand
MAX_STREAM = 1 << 16  # blocks per call: 256 KiB of int32 in SMEM


def _set_tile(n_sets: int) -> int:
    """Rows per program: the largest power-of-two divisor of ``n_sets`` up
    to ``_MAX_TILE``, or the whole axis when that divisor is not a multiple
    of the 8-row sublane tile."""
    tile = _MAX_TILE
    while n_sets % tile:
        tile //= 2
    return tile if tile % 8 == 0 else n_sets


def _update_kernel(blocks_ref, tags_ref, cnt_ref, out_tags_ref, out_cnt_ref,
                   *, n_sets: int, tile: int):
    t0 = pl.program_id(0) * tile
    out_tags_ref[...] = tags_ref[...]
    out_cnt_ref[...] = cnt_ref[...]
    ways = tags_ref.shape[1]
    wi = jax.lax.broadcasted_iota(jnp.int32, (1, ways), 1)

    def first(mask):
        return jnp.min(jnp.where(mask, wi, ways)).astype(jnp.int32)

    def body(i, carry):
        b = blocks_ref[i]
        s = b % n_sets
        local = s - t0
        mine = (b >= 0) & (local >= 0) & (local < tile)
        idx = jnp.where(mine, local, 0)
        row_t = out_tags_ref[pl.ds(idx, 1), :]
        row_c = out_cnt_ref[pl.ds(idx, 1), :]
        hit = row_t == b
        is_hit = hit.any()
        empty = row_t == -1
        min_c = row_c.min()
        ins = jnp.where(empty.any(), first(empty), first(row_c == min_c))
        way = jnp.where(is_hit, first(hit), ins)
        sel = (wi == way) & mine
        base = jnp.where(is_hit, jnp.sum(jnp.where(wi == way, row_c, 0)), 0)
        out_tags_ref[pl.ds(idx, 1), :] = jnp.where(sel, b, row_t)
        out_cnt_ref[pl.ds(idx, 1), :] = jnp.where(
            sel, jnp.minimum(base + 1, COUNTER_MAX), row_c
        )
        return carry

    jax.lax.fori_loop(0, blocks_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def freq_update(tags, counters, blocks, *, interpret: bool = False):
    """Stream ``blocks`` (int32 (N,), -1 = no-op padding) through the table;
    returns the updated (tags, counters), both int32 (S, W)."""
    tags = jnp.asarray(tags, jnp.int32)
    counters = jnp.asarray(counters, jnp.int32)
    blocks = jnp.asarray(blocks, jnp.int32)
    n_sets, ways = tags.shape
    tile = _set_tile(n_sets)
    return pl.pallas_call(
        functools.partial(_update_kernel, n_sets=n_sets, tile=tile),
        grid=(n_sets // tile,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((tile, ways), lambda i: (i, 0)),
            pl.BlockSpec((tile, ways), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((tile, ways), lambda i: (i, 0)),
            pl.BlockSpec((tile, ways), lambda i: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n_sets, ways), jnp.int32),
            jax.ShapeDtypeStruct((n_sets, ways), jnp.int32),
        ],
        interpret=interpret,
    )(blocks, tags, counters)


def _lookup_kernel(blocks_ref, tags_ref, cnt_ref, out_ref, *, n_sets: int):
    ways = tags_ref.shape[1]
    wi = jax.lax.broadcasted_iota(jnp.int32, (1, ways), 1)

    def body(i, carry):
        b = blocks_ref[i]
        s = b % n_sets
        row_t = tags_ref[pl.ds(s, 1), :]
        row_c = cnt_ref[pl.ds(s, 1), :]
        hit = row_t == b
        # first-hit way, exactly lookup_many's ``hit.argmax``
        way = jnp.min(jnp.where(hit, wi, ways)).astype(jnp.int32)
        cnt = jnp.sum(jnp.where(wi == jnp.where(hit.any(), way, 0), row_c, 0))
        out_ref[i] = jnp.where(hit.any(), cnt, -1)
        return carry

    jax.lax.fori_loop(0, blocks_ref.shape[0], body, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def freq_lookup(tags, counters, blocks, *, interpret: bool = False):
    """Current counter per block (int32 (N,)), -1 on miss."""
    tags = jnp.asarray(tags, jnp.int32)
    counters = jnp.asarray(counters, jnp.int32)
    blocks = jnp.asarray(blocks, jnp.int32)
    n_sets = tags.shape[0]
    return pl.pallas_call(
        functools.partial(_lookup_kernel, n_sets=n_sets),
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
        + [pl.BlockSpec(memory_space=pltpu.VMEM)] * 2,
        out_specs=pl.BlockSpec(memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct(blocks.shape, jnp.int32),
        interpret=interpret,
    )(blocks, tags, counters)
