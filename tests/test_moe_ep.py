"""Expert-parallel (shard_map + all-to-all) MoE dispatch must match the
pure-pjit scatter dispatch when capacity is generous (no drops): run both in
a subprocess with 8 placeholder devices on a (data=2, model=4) mesh."""
import os
import subprocess
import sys
from pathlib import Path

SRC = str(Path(__file__).resolve().parent.parent / "src")

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_smoke_config
from repro.distributed import sharding
from repro.launch.mesh import auto_mesh
from repro.models import moe
from repro.models.params import init_params

cfg = get_smoke_config("olmoe-1b-7b").replace(num_experts=8, top_k=2, capacity_factor=8.0)
specs = moe.moe_specs(cfg)
params = init_params(jax.random.key(0), specs, jnp.float32)
mesh = auto_mesh((2, 4), ("data", "model"))
x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model), jnp.float32)

with sharding.use_mesh_rules(mesh):
    os.environ["REPRO_MOE_IMPL"] = "scatter"
    y_scatter, aux_s = jax.jit(lambda p, xx: moe.moe_ffn(p, xx, cfg))(params, x)
    os.environ["REPRO_MOE_IMPL"] = "ep"
    y_ep, aux_e = jax.jit(lambda p, xx: moe.moe_ffn(p, xx, cfg))(params, x)

err = float(jnp.abs(y_scatter - y_ep).max())
ref = float(jnp.abs(y_scatter).max())
aux_err = abs(float(aux_s) - float(aux_e))
print(f"RESULT err={err:.2e} ref={ref:.2e} aux_err={aux_err:.2e}")
assert err <= 1e-4 * max(ref, 1.0), (err, ref)
assert aux_err < 1e-4, aux_err
print("OK")
"""


def test_ep_matches_scatter_multidevice():
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env, capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "OK" in out.stdout
