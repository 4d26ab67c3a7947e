"""Host spans around the calls into each layer, written into the
profiler's trace (``jax.profiler.TraceAnnotation``) so that they share
its clock with the device's operations.  Off (no cost but a call) unless
the run is traced."""
from __future__ import annotations

import contextlib

PREFIX = "bench:"


class Spans:
    def __init__(self, on: bool):
        self.on = on

    def __call__(self, name: str):
        if not self.on:
            return contextlib.nullcontext()
        import jax

        return jax.profiler.TraceAnnotation(PREFIX + name)

    def open(self, name: str):
        """An entered span that the caller closes with ``__exit__``: for a
        span that runs across several calls, as a manager round does."""
        s = self(name)
        s.__enter__()
        return s
