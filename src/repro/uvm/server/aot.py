"""Compile-once AOT export/reload of the trainer's jitted executables.

A fresh process pays ~17s of jax tracing + lowering before its first
serve dispatch (one trace per jitted scan per shape bucket).  This module
serializes each traced executable with :mod:`jax.export` the first time a
(function, argument-shapes, static-flags) combination runs and reloads
the StableHLO artifact from disk on the next process start — tracing and
lowering are skipped entirely (XLA still compiles the deserialized
module, which is the smaller share).  The exported path is bit-identical
to the jit path; ``tests/test_server.py`` pins that equality.

Usage::

    trainer = Trainer(pcfg, tcfg, kind)
    enable_aot(trainer, "~/.cache/repro-aot")   # wraps the jitted scans

Every failure in the AOT path (unserializable config, backend mismatch,
a stale artifact) falls back silently to the wrapped jit function and is
counted on :class:`AotCache`; serving never depends on the cache being
healthy.
"""
from __future__ import annotations

import hashlib
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
from jax import export as jax_export

from repro.optim.adamw import OptState

#: the jitted Trainer instance attributes worth exporting (the scans —
#: per-step fns are only used by `old_features`, too cheap to matter)
_EXPORTABLE = ("_eval_scan", "_train_scan", "_eval_scan_many", "_train_scan_many")

_MISSING = object()
_registered = False


def _ensure_registered() -> None:
    """jax.export serializes pytrees by registered structure; the
    optimizer state is a custom NamedTuple it must be taught once."""
    global _registered
    if not _registered:
        jax_export.register_namedtuple_serialization(
            OptState, serialized_name="repro.optim.adamw.OptState")
        _registered = True


class AotCache:
    """On-disk store of serialized exports, keyed by content signature."""

    def __init__(self, root):
        self.root = Path(os.path.expanduser(str(root)))
        self.root.mkdir(parents=True, exist_ok=True)
        self.hits = 0  # artifacts reloaded from disk (trace skipped)
        self.misses = 0  # traced + exported this process
        self.fallbacks = 0  # AOT path failed; jit path served the call

    def stats(self) -> dict:
        return {"hits": self.hits, "misses": self.misses, "fallbacks": self.fallbacks}


def _canon(args):
    """Commit every leaf to a strongly-typed device array so the export
    specs and the later calls agree on dtypes (python scalars arrive
    weakly typed; `astype` onto the same dtype strips the weak flag)."""
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a).astype(jnp.asarray(a).dtype), args)


class _AotFn:
    """Wrapper over one jitted function: export-or-reload per call
    signature, jit fallback on any AOT failure."""

    def __init__(self, jit_fn, name: str, cache: AotCache, closure_sig: str):
        self._jit_fn = jit_fn
        self._name = name
        self._cache = cache
        self._closure_sig = closure_sig
        self._loaded: dict = {}  # key -> exported | None (poisoned: use jit)

    def _key(self, args, static: dict) -> str:
        leaves, treedef = jax.tree_util.tree_flatten(args)
        sig = json.dumps({
            "fn": self._name,
            "closure": self._closure_sig,
            "static": {k: repr(v) for k, v in sorted(static.items())},
            "tree": str(treedef),
            "leaves": [(str(l.shape), str(l.dtype)) for l in leaves],
            "jax": jax.__version__,
            "backend": jax.default_backend(),
        }, sort_keys=True)
        return hashlib.sha256(sig.encode()).hexdigest()[:32]

    def _load_or_export(self, key: str, args, static: dict):
        path = self.root_path(key)
        try:
            _ensure_registered()
            if path.exists():
                exported = jax_export.deserialize(path.read_bytes())
                self._cache.hits += 1
                return exported
            specs = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), args)
            exported = jax_export.export(self._jit_fn)(*specs, **static)
            tmp = path.with_suffix(".tmp")
            tmp.write_bytes(exported.serialize())
            tmp.replace(path)  # atomic publish: concurrent processes race safely
            self._cache.misses += 1
            return exported
        except Exception:  # noqa: BLE001 — any AOT failure means "use jit"
            self._cache.fallbacks += 1
            return None

    def root_path(self, key: str) -> Path:
        return self._cache.root / f"{self._name}-{key}.jaxexport"

    def __call__(self, *args, **static):
        try:
            args = _canon(args)
            key = self._key(args, static)
        except Exception:  # noqa: BLE001
            self._cache.fallbacks += 1
            return self._jit_fn(*args, **static)
        exported = self._loaded.get(key, _MISSING)
        if exported is _MISSING:
            exported = self._load_or_export(key, args, static)
            self._loaded[key] = exported
        if exported is None:
            return self._jit_fn(*args, **static)
        try:
            return exported.call(*args)
        except Exception:  # noqa: BLE001 — e.g. an artifact from another backend
            self._cache.fallbacks += 1
            self._loaded[key] = None
            return self._jit_fn(*args, **static)


def enable_aot(trainer, cache) -> AotCache:
    """Wrap ``trainer``'s jitted scans with the export-or-reload path.

    Wrapping is per-instance (the process-wide ``_TRAINER_FN_CACHE`` stays
    untouched) and idempotent.  Returns the :class:`AotCache` (also set as
    ``trainer.aot_cache``) so callers can report hit/miss/fallback counts.
    """
    cache = cache if isinstance(cache, AotCache) else AotCache(cache)
    closure_sig = f"{trainer.pcfg!r}|{trainer.tcfg!r}|{trainer.kind}"
    for name in _EXPORTABLE:
        fn = getattr(trainer, name)
        if not isinstance(fn, _AotFn):
            setattr(trainer, name, _AotFn(fn, name, cache, closure_sig))
    trainer.aot_cache = cache
    return cache
