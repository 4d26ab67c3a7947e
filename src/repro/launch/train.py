"""Config-driven distributed trainer.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
        --steps 50 --batch 8 --seq 128 --out /tmp/run1

Features: sharded jit train step (resolver shardings), gradient accumulation,
deterministic resumable data, atomic checkpointing + crash recovery,
straggler-policy gradient renormalisation, optional int8 pod-axis gradient
compression, elastic mesh planning from whatever devices exist.

The same entry point trains the PAPER'S PREDICTOR at fleet scale:
    python -m repro.launch.train --arch predictor-paper --steps 200
(its data pipeline is the UVM trace corpus instead of the token stream).
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint.checkpointer import Checkpointer
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, TokenPipeline
from repro.distributed import sharding
from repro.distributed.elastic import ElasticController, StragglerPolicy
from repro.launch import mesh as meshmod
from repro.models import lm
from repro.optim import adamw


def make_mesh_from_devices(prefer_model: int = 1):
    n = len(jax.devices())
    ctl = ElasticController(n, prefer_model=prefer_model)
    pod, data, model = ctl.mesh_shape
    dims, axes = [], []
    for d, a in zip((pod, data, model), ("pod", "data", "model")):
        if d > 1 or a == "data":
            dims.append(d)
            axes.append(a)
    return meshmod.auto_mesh(tuple(dims), tuple(axes)), ctl


def train_lm(args) -> dict:
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh, ctl = make_mesh_from_devices(prefer_model=args.tp)
    n_shards = ctl.data_shards

    dcfg = DataConfig(vocab_size=cfg.vocab_size, batch=args.batch, seq_len=args.seq, seed=args.seed)
    pipe = TokenPipeline(dcfg)
    opt = adamw.adamw(adamw.cosine_schedule(args.lr, args.warmup, args.steps), weight_decay=0.1)
    accum = max(args.accum, 1)
    straggler = StragglerPolicy(n_microbatches=accum)

    specs = lm.param_specs(cfg, max_seq=args.seq)
    params_sh = sharding.params_shardings(mesh, specs)
    rng = jax.random.key(args.seed)
    with sharding.use_mesh_rules(mesh):
        params = jax.jit(lambda r: lm.init(r, cfg, max_seq=args.seq), out_shardings=params_sh)(rng)
        opt_state = jax.jit(opt.init, out_shardings=adamw.OptState(m=params_sh, v=params_sh))(params)

    grad_step = jax.jit(lm.make_grad_step(cfg))
    apply_fn = jax.jit(
        lambda p, o, g, s: _apply(opt, p, o, g, s),
        donate_argnums=(0, 1),
    )

    ckpt = Checkpointer(args.out, keep=3)
    ckpt.clean_tmp()
    start = 0
    if args.resume and ckpt.latest_step() is not None:
        start, tree, extra = ckpt.restore(shardings=params_sh)
        params = {k: v for k, v in tree.items() if not k.startswith("opt/")}
        m = {k[len("opt/m/"):]: v for k, v in tree.items() if k.startswith("opt/m/")}
        v = {k[len("opt/v/"):]: v for k, v in tree.items() if k.startswith("opt/v/")}
        if m:
            opt_state = adamw.OptState(m=m, v=v)
        print(f"resumed from step {start}")

    log = []
    t0 = time.time()
    with sharding.use_mesh_rules(mesh):
        for step in range(start, args.steps):
            grads = None
            landed = 0
            for micro in range(accum):
                batch_np = pipe.get(step * accum + micro)
                batch = {"tokens": jnp.asarray(batch_np)}
                g, metrics = grad_step(params, batch)
                grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
                landed += 1
                if args.simulate_straggler_drop and micro == accum - 1 and step % 7 == 3:
                    landed -= 1  # deadline missed: drop the last microbatch
                    grads = jax.tree.map(lambda a, b: a - b, grads, g)
            grads, ok = straggler.combine(grads, max(landed, 1))
            grads = jax.tree.map(lambda g_: g_ / max(landed, 1), grads)
            params, opt_state = apply_fn(params, opt_state, grads, step)
            if step % args.log_every == 0 or step == args.steps - 1:
                rec = {"step": step, "loss": float(metrics["total_loss"]), "t": round(time.time() - t0, 1)}
                log.append(rec)
                print(rec)
            if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
                tree = dict(params)
                tree.update({f"opt/m/{k}": v for k, v in opt_state.m.items()})
                tree.update({f"opt/v/{k}": v for k, v in opt_state.v.items()})
                ckpt.save(step + 1, tree, extra={"arch": cfg.name})
    return {"final_loss": log[-1]["loss"] if log else None, "log": log, "mesh": ctl.mesh_shape}


def _apply(opt, params, opt_state, grads, step):
    updates, opt_state, _ = opt.update(grads, opt_state, params, step)
    return adamw.apply_updates(params, updates), opt_state


def train_predictor(args) -> dict:
    """Fleet-scale training of the paper's predictor on a trace corpus."""
    from repro.configs.predictor_paper import CONFIG, SMOKE
    from repro.core.features import DeltaVocab, FeatureStream
    from repro.core.incremental import TrainConfig, Trainer
    from repro.uvm.trace import BENCHMARKS

    pcfg = SMOKE if args.smoke else CONFIG
    tcfg = TrainConfig(batch_size=args.batch, lr=args.lr, epochs=1)
    trainer = Trainer(pcfg, tcfg, kind="transformer")
    corpus = [fn(scale=0.25, seed=100 + i) for i, fn in enumerate(BENCHMARKS.values())]
    from repro.core.model_table import Entry

    entry = Entry(params=trainer.new_params(args.seed))
    losses = []
    for step in range(args.steps):
        tr = corpus[step % len(corpus)]
        vocab = DeltaVocab(pcfg.delta_vocab)
        stream = FeatureStream(tr, vocab, pcfg.history, page_vocab=pcfg.page_vocab, pc_vocab=pcfg.pc_vocab, tb_vocab=pcfg.tb_vocab)
        fs = stream.windows(0, min(len(tr), 2048))
        entry = trainer.train_group(entry, fs, max(vocab.n_classes, 2))
        corr, _ = trainer.evaluate(entry.params, fs, max(vocab.n_classes, 2))
        losses.append(float(corr.mean()))
        if step % args.log_every == 0:
            print({"step": step, "train_top1": losses[-1]})
    return {"final_top1": losses[-1], "log": losses}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true", help="use the reduced config")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="/tmp/repro_train")
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--simulate-straggler-drop", action="store_true")
    args = ap.parse_args(argv)
    if args.arch == "predictor-paper":
        out = train_predictor(args)
    else:
        out = train_lm(args)
    print(json.dumps({k: v for k, v in out.items() if k != "log"}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
