"""Dispatcher for the prediction-frequency-table kernels.

Pads block streams to power-of-two buckets (update pads with the ``-1``
no-op sentinel; lookup results are sliced back to the real length) so
repeated manager batches of drifting sizes reuse a few compiled kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.freq_table import kernel, ref
from repro.util import pow2_bucket


def default_interpret() -> bool:
    """Interpret mode on backends with no Mosaic lowering (CPU CI)."""
    return jax.default_backend() == "cpu"


def _pad_blocks(blocks, fill: int):
    b = np.asarray(blocks, np.int64).ravel()
    if b.size and not (-1 <= b.min() and b.max() < 2**31):
        raise ValueError("freq_table kernels take int32 block ids (>= -1)")
    n = pow2_bucket(max(b.size, 1), 64)
    out = np.full(n, fill, np.int32)
    out[: b.size] = b
    return out, b.size


def _chunks(b: np.ndarray) -> list[np.ndarray]:
    """A padded stream in kernel-sized pieces (pow2 padding makes every
    piece of a long stream exactly ``MAX_STREAM``, so one compile serves)."""
    return [b[i : i + kernel.MAX_STREAM] for i in range(0, len(b), kernel.MAX_STREAM)]


def freq_update(tags, counters, blocks, *, use_kernel=False, interpret=False):
    """Updated (tags, counters) after streaming ``blocks`` (any int dtype)."""
    b, _ = _pad_blocks(blocks, -1)
    if use_kernel:
        for chunk in _chunks(b):
            tags, counters = kernel.freq_update(tags, counters, chunk, interpret=interpret)
        return tags, counters
    return ref.freq_update_ref(tags, counters, b)


def freq_lookup(tags, counters, blocks, *, use_kernel=False, interpret=False):
    """Counter per block, -1 on miss (int32, same length as ``blocks``)."""
    b, n = _pad_blocks(blocks, -1)
    if use_kernel:
        out = jnp.concatenate([kernel.freq_lookup(tags, counters, c, interpret=interpret)
                               for c in _chunks(b)])
    else:
        out = ref.freq_lookup_ref(jnp.asarray(tags, jnp.int32),
                                  jnp.asarray(counters, jnp.int32), b)
    return out[:n]
