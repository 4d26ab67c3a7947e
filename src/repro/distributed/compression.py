"""Gradient compression for cross-pod all-reduce: int8 quantisation with
error feedback (EF-SGD style).

At 1000+-node scale the pod axis crosses slow DCI links; quantising the
gradient all-reduce 4x (fp32 -> int8 + per-block fp32 scale) cuts that
traffic proportionally. Error feedback accumulates the quantisation residual
locally and re-injects it next step, preserving convergence (Karimireddy et
al., 2019).

`compressed_psum` runs inside shard_map: quantise -> psum int32 -> dequantise.
(int8 values are summed in int32 to avoid overflow across <=2^15 shards.)
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def quantize(x: jax.Array, block: int = 256):
    """Symmetric per-block int8. Returns (q, scale, shape)."""
    flat = x.reshape(-1)
    pad = (-len(flat)) % block
    flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.max(jnp.abs(blocks), axis=1, keepdims=True) / 127.0
    q = jnp.clip(jnp.round(blocks / jnp.maximum(scale, 1e-12)), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32), x.shape


def dequantize(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    return flat[: int(jnp.prod(jnp.asarray(shape)))].reshape(shape) if flat.size else flat.reshape(shape)


def _deq_size(shape):
    n = 1
    for d in shape:
        n *= d
    return n


def compressed_psum(x: jax.Array, axis_name: str, block: int = 256):
    """Quantised all-reduce over a mesh axis (use inside shard_map)."""
    q, scale, shape = quantize(x, block)
    qsum = jax.lax.psum(q.astype(jnp.int32), axis_name)
    ssum = jax.lax.psum(scale, axis_name)  # conservative shared scale
    n = jax.lax.psum(1, axis_name)
    # average of per-shard scales; dequantise the summed ints with it
    avg_scale = ssum / n
    flat = (qsum.astype(jnp.float32) * avg_scale).reshape(-1)
    return flat[: _deq_size(shape)].reshape(shape)


class ErrorFeedback:
    """Residual accumulator: g_t' = g_t + e_{t-1}; e_t = g_t' - Q(g_t')."""

    @staticmethod
    def init(params):
        return jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    @staticmethod
    def apply(grads, residual, block: int = 256):
        """Returns (quantised-effective grads, new residual)."""

        def one(g, e):
            g = g.astype(jnp.float32) + e
            q, s, shp = quantize(g, block)
            deq = dequantize(q, s, shp)
            return deq, g - deq

        flat = jax.tree.map(one, grads, residual)
        comp = jax.tree.map(lambda t: t[0], flat, is_leaf=lambda t: isinstance(t, tuple))
        res = jax.tree.map(lambda t: t[1], flat, is_leaf=lambda t: isinstance(t, tuple))
        return comp, res


def make_compressed_allreduce(mesh, axis_name: str = "pod", block: int = 256):
    """Grad all-reduce over `axis_name` in int8, other axes untouched.

    Usage in the trainer: grads are already reduced over data/model by XLA
    (from the loss), and the POD axis reduction is done explicitly here so it
    can be compressed.
    """

    def allreduce(tree):
        def one(g):
            spec = P(*([None] * g.ndim))

            def f(x):
                return compressed_psum(x, axis_name, block) / jax.lax.psum(1, axis_name)

            return jax.shard_map(f, mesh=mesh, in_specs=spec, out_specs=spec)(g)

        return jax.tree.map(one, tree)

    return allreduce
