"""The configuration's program objects, and the pretrained table on disk.

The table file is one ``.npz``: for each slot ``s`` the parameters under
``s/params/<leaf>``, the Adam moments under ``s/m/<leaf>`` and
``s/v/<leaf>``, and the slot's counters (``step``, ``n_updates``,
``last_acc``) in a JSON string under ``meta``.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parents[1]


def ensure_src_on_path() -> None:
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def predictor_config(cfg: dict):
    ensure_src_on_path()
    from repro.configs.predictor_paper import PredictorConfig

    return PredictorConfig(**cfg["predictor"])


def train_config(cfg: dict):
    ensure_src_on_path()
    from repro.core.incremental import TrainConfig

    return TrainConfig(**cfg["train"])


def table_path(cfg: dict) -> Path:
    return ROOT / cfg["pretrain"]["table"]


def save_table(table, path: Path) -> None:
    arrays, meta = {}, {"n_slots": table.n_slots, "slots": {}}
    for s, e in table.slots.items():
        for k, a in e.params.items():
            arrays[f"{s}/params/{k}"] = np.asarray(a)
        if e.opt_state is not None:
            for k in e.params:
                arrays[f"{s}/m/{k}"] = np.asarray(e.opt_state.m[k])
                arrays[f"{s}/v/{k}"] = np.asarray(e.opt_state.v[k])
        meta["slots"][str(s)] = {"step": int(e.step), "n_updates": int(e.n_updates),
                                 "last_acc": float(e.last_acc), "opt_state": e.opt_state is not None}
    path.parent.mkdir(parents=True, exist_ok=True)
    np.savez_compressed(path, meta=np.array(json.dumps(meta)), **arrays)


def load_table(path: Path, trainer):
    """A ``ModelTable`` of the stored slots, every array on the device in
    one transfer."""
    import jax

    ensure_src_on_path()
    from repro.core.model_table import Entry, ModelTable
    from repro.optim.adamw import OptState

    with np.load(path) as z:
        meta = json.loads(str(z["meta"]))
        arrays = {k: z[k] for k in z.files if k != "meta"}
    dev = jax.device_put(arrays)
    table = ModelTable(lambda s: trainer.new_params(s), n_slots=meta["n_slots"])
    for s, m in meta["slots"].items():
        leaf = lambda kind: {k.split("/", 2)[2]: a for k, a in dev.items() if k.startswith(f"{s}/{kind}/")}
        opt = OptState(m=leaf("m"), v=leaf("v")) if m["opt_state"] else None
        table.slots[int(s)] = Entry(params=leaf("params"), opt_state=opt, step=m["step"],
                                    n_updates=m["n_updates"], last_acc=m["last_acc"])
    return table
