"""The program's spans, on the profiler's clock.

* :func:`span` names a stretch of host work: a
  ``jax.profiler.TraceAnnotation`` called ``repro:<name>``, so it lands in
  a profiler trace beside the device's programs and shares their clock.
  Keyword ids (``round=3``) travel as the event's stats.  It is always on:
  with no profiler recording it costs well under a microsecond.
* :func:`to_host`: every blocking device-to-host pull on the manager's
  rounds and the simulator's sweeps goes through here, so each is spanned
  (``repro:sync.<site>``) and a traced window counts them.

This is the program's only tracing module; it has no switch.
"""
from __future__ import annotations

import functools

import jax

PREFIX = "repro:"


def span(name: str, **ids):
    """A context manager that records ``repro:<name>`` while a profiler
    traces, with ``ids`` as the event's stats."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **ids)


def spanned(name: str):
    """Decorator form of :func:`span`: the whole call is the span."""

    def wrap(fn):
        @functools.wraps(fn)
        def inner(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)

        return inner

    return wrap


def to_host(x, site: str):
    """``jax.device_get(x)`` inside the span ``sync.<site>``, which holds
    the wait for the device as well as the copy."""
    with span("sync." + site):
        return jax.device_get(x)
