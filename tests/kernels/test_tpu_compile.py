"""Compile the main path's Pallas kernels for a described TPU v5e.

No chip is needed: the TPU compiler is installed with jaxlib and compiles
for a topology that is described, not attached.  These compiles catch
what interpret mode cannot (integer argmax, scalar reads from VMEM,
unaligned block shapes, SMEM overflow), and every test asserts that the
compiled program really holds a Mosaic kernel (``tpu_custom_call``).

The topology is described inside a module fixture, never at import time:
only one process at a time may load the TPU library, and every pytest
worker imports every test file.  Keep these tests in this one file, so
that one worker loads the library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.evict_select import kernel as evict_kernel
from repro.kernels.freq_table import kernel as freq_kernel
from repro.uvm import registry
from repro.uvm import simulator as S

LANES = 16  # one EQUIV_CELLS sweep


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def compile_text(topo):
    """``compile_text(fn, *shapes)``: the compiled TPU program's text.

    The persistent compile cache is off meanwhile: an entry compiled for a
    described chip cannot be read back without one, and the read warning
    would fail the suite (``filterwarnings = error``)."""
    from jax.experimental.compilation_cache import compilation_cache

    one_chip = SingleDeviceSharding(topo.devices[0])

    def on_chip(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    def compile_text(fn, *shapes):
        args = jax.tree.map(on_chip, shapes)
        return jax.jit(fn).lower(*args).compile().as_text()

    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield compile_text
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _i32(*shape):
    return jax.ShapeDtypeStruct(shape, jnp.int32)


@pytest.mark.parametrize("n_keys", [3, 4])
@pytest.mark.parametrize("nb", [128, 256, 512])
def test_evict_select_compiles(compile_text, nb, n_keys):
    def select(cand, *rest):
        return evict_kernel.evict_select(cand, rest[:-1], rest[-1])

    text = compile_text(select, jax.ShapeDtypeStruct((nb,), jnp.bool_),
                        *[_i32(nb)] * n_keys, _i32())
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("nb", [128, 256, 512])
def test_evict_select_vmapped_compiles(compile_text, nb):
    select = jax.vmap(lambda c, k0, k1, k2, k3, n: evict_kernel.evict_select(c, (k0, k1, k2, k3), n))
    text = compile_text(select, jax.ShapeDtypeStruct((LANES, nb), jnp.bool_),
                        *[_i32(LANES, nb)] * 4, _i32(LANES))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("qos", [False, True], ids=["policy-keys", "qos-leading-key"])
def test_simulator_kernel_scan_compiles(compile_text, qos):
    """The event scan with kernel victim selection, at Hotspot's paper-scale
    geometry (256-block bucket, 8192-event bucket) over a 16-lane sweep."""
    nb, events = 256, 8192
    run_events = S._jits_for(registry.policy_branches(), registry.prefetch_branches(),
                             kernels=True, interpret=False)[0]
    states = jax.eval_shape(lambda: S._stack_states([S.init_state(nb)] * LANES))
    lane = [_i32(LANES)] * 4  # capacity, policy, prefetch, n_valid
    ep = (_i32(LANES, nb),) if qos else ()
    text = compile_text(run_events, states, *[_i32(events)] * 5, *lane, *ep)
    assert "tpu_custom_call" in text


def test_freq_update_compiles(compile_text):
    text = compile_text(freq_kernel.freq_update, _i32(1024, 16), _i32(1024, 16), _i32(2048))
    assert "tpu_custom_call" in text


def test_freq_lookup_compiles(compile_text):
    text = compile_text(freq_kernel.freq_lookup, _i32(1024, 16), _i32(1024, 16), _i32(2048))
    assert "tpu_custom_call" in text
