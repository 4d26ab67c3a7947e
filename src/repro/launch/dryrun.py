import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=" + os.environ.get("REPRO_DRYRUN_DEVICES", "512") + " " + os.environ.get("XLA_FLAGS", "")
"""Multi-pod dry-run: prove the distribution config is coherent without hardware.

For every (architecture x input shape) cell this lowers + compiles the real
step function (train_step / prefill / serve decode_step) against
ShapeDtypeStruct stand-ins on the production mesh — (data=16, model=16)
single pod and (pod=2, data=16, model=16) multi-pod — then records
memory_analysis / cost_analysis / roofline terms to JSON for EXPERIMENTS.md.

The two lines above MUST run before any jax-importing module: jax locks the
device count on first init, and only the dry-run should see 512 placeholder
devices.

Usage:
    python -m repro.launch.dryrun --arch qwen3-0.6b --shape train_4k
    python -m repro.launch.dryrun --all --mesh single --out experiments/dryrun
    python -m repro.launch.dryrun --all --mesh multi
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, SHAPES, cell_supported, get_config
from repro.distributed import sharding
from repro.launch import mesh as meshmod
from repro.launch import roofline
from repro.models import lm
from repro.models import params as prm
from repro.optim import adamw


def _abstract_tree(specs: dict, dtype=jnp.float32):
    return {p: jax.ShapeDtypeStruct(s.shape, dtype) for p, s in specs.items()}


def _sharding_tree(mesh, specs: dict, rules=None):
    return sharding.params_shardings(mesh, specs, rules)


def build_cell(cfg, shape, mesh, rules=None):
    """Returns (fn, example_args, in_shardings, donate) for jit."""
    pspecs = lm.param_specs(cfg, max_seq=shape.seq_len)
    params_abs = _abstract_tree(pspecs)
    params_sh = _sharding_tree(mesh, pspecs, rules)
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    bspecs = lm.batch_specs(cfg, shape)
    baxes = lm.batch_axes(cfg, shape)
    batch_sh = {
        k: sharding.named_sharding(mesh, baxes[k], bspecs[k].shape, rules) for k in bspecs
    }

    if shape.kind == "train":
        opt = adamw.adamw(adamw.cosine_schedule(3e-4, 100, 10_000))
        step_fn = lm.make_train_step(cfg, opt)
        opt_abs = adamw.OptState(m=params_abs, v=params_abs)
        opt_sh = adamw.OptState(m=params_sh, v=params_sh)
        args = (params_abs, opt_abs, bspecs, jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (params_sh, opt_sh, batch_sh, repl)
        donate = (0, 1)
        return step_fn, args, in_sh, donate
    if shape.kind == "prefill":
        fn = lm.make_prefill(cfg)
        args = (params_abs, bspecs)
        in_sh = (params_sh, batch_sh)
        return fn, args, in_sh, ()
    # decode
    cspecs = lm.cache_specs(cfg, shape)
    cache_abs = {p: jax.ShapeDtypeStruct(s.shape, lm.cache_dtype(p, cfg)) for p, s in cspecs.items()}
    cache_sh = _sharding_tree(mesh, cspecs, rules)
    fn = lm.make_decode_step(cfg)
    args = (params_abs, bspecs, cache_abs)
    in_sh = (params_sh, batch_sh, cache_sh)
    return fn, args, in_sh, (2,)


def run_cell(arch: str, shape_name: str, mesh_kind: str, out_dir: Path, *, force=False, rules=None, tag="", kv_quant="none") -> dict:
    cfg = get_config(arch)
    if kv_quant != "none":
        cfg = cfg.replace(kv_quant=kv_quant)
    shape = SHAPES[shape_name]
    out_dir.mkdir(parents=True, exist_ok=True)
    out_path = out_dir / f"{arch}__{shape_name}__{mesh_kind}{tag}.json"
    if out_path.exists() and not force:
        rec = json.loads(out_path.read_text())
        print(f"[cached] {arch} x {shape_name} x {mesh_kind}: {rec.get('status')}")
        return rec

    supported, reason = cell_supported(cfg, shape)
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_kind, "tag": tag}
    if not supported:
        rec.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(rec, indent=2))
        print(f"[skip]   {arch} x {shape_name}: {reason}")
        return rec

    mesh = meshmod.make_production_mesh(multi_pod=(mesh_kind == "multi")) if mesh_kind in ("single", "multi") else meshmod.make_mesh(mesh_kind)
    t0 = time.time()
    try:
        fn, args, in_sh, donate = build_cell(cfg, shape, mesh, rules)
        with sharding.use_mesh_rules(mesh, rules):
            lowered = jax.jit(fn, in_shardings=in_sh, donate_argnums=donate).lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        try:
            mem = compiled.memory_analysis()
            print(mem)
            mem_rec = {
                "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
                "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
                "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
                "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
                "generated_code_bytes": int(getattr(mem, "generated_code_size_in_bytes", 0)),
            }
        except Exception as e:  # CPU backend may not implement it
            mem_rec = {"error": str(e)}

        cost = dict(compiled.cost_analysis() or {})
        print({k: cost[k] for k in ("flops", "bytes accessed") if k in cost})

        tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode" else 1)
        n_active = lm.active_param_count(cfg, max_seq=shape.seq_len)
        factor = 6 if shape.kind == "train" else 2
        model_flops = factor * n_active * tokens

        hlo = compiled.as_text()
        from repro.models.layers import ATTN_KV_CHUNK

        rl = roofline.analyze(compiled, mesh, model_flops, hlo_text=hlo, attn_score_trailing=ATTN_KV_CHUNK)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            params=lm.param_count(cfg, max_seq=shape.seq_len),
            active_params=n_active,
            tokens_per_step=tokens,
            memory_analysis=mem_rec,
            cost_flops=cost.get("flops", 0.0),
            cost_bytes=cost.get("bytes accessed", 0.0),
            roofline=rl.table_row(),
            collectives=rl.coll.bytes_by_kind,
            top_traffic=[
                {"bytes": b, "mult": m, "op": o, "shape": s} for b, m, o, s in rl.top_traffic
            ],
            hlo_bytes_len=len(hlo),
        )
        print(
            f"[ok]     {arch} x {shape_name} x {mesh_kind}{tag}: "
            f"compute={rl.compute_s:.4e}s memory={rl.memory_s:.4e}s "
            f"collective={rl.collective_s:.4e}s bottleneck={rl.bottleneck} "
            f"useful={rl.useful_ratio:.2f} (compile {t_compile:.1f}s)"
        )
    except Exception:
        rec.update(status="error", error=traceback.format_exc())
        print(f"[ERROR]  {arch} x {shape_name} x {mesh_kind}{tag}:\n{rec['error']}", file=sys.stderr)
    out_path.write_text(json.dumps(rec, indent=2))
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=ARCHS + ["all"], default="all")
    ap.add_argument("--shape", choices=list(SHAPES) + ["all"], default="all")
    ap.add_argument("--mesh", default="single", help="single | multi | WxH | pod:PxWxH")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--rules", choices=["default", "fsdp_only"], default="default")
    ap.add_argument("--tag", default="", help="suffix for experiment records (hillclimb variants)")
    ap.add_argument("--kv-quant", choices=["none", "int8"], default="none")
    args = ap.parse_args(argv)

    rules = sharding.FSDP_ONLY_RULES if args.rules == "fsdp_only" else None
    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    out_dir = Path(args.out)

    n_err = 0
    for arch in archs:
        for shape_name in shapes:
            rec = run_cell(arch, shape_name, args.mesh, out_dir, force=args.force, rules=rules, tag=args.tag, kv_quant=args.kv_quant)
            n_err += rec.get("status") == "error"
    print(f"done; {n_err} errors")
    return 1 if n_err else 0


if __name__ == "__main__":
    raise SystemExit(main())
