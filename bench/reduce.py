"""From a profiler trace to the numbers the per-layer metrics read.

Two stages, so the second can be checked without a profiler:

1. :func:`load` reads an ``.xplane.pb`` (``jax.profiler.ProfileData``)
   into plain tuples: the programs each device plane ran (its ``XLA
   Modules`` line: one event per execution of a jitted program), and the
   benchmark's host spans.
2. :class:`View` holds those tuples for one traced window and reduces
   them: busy time as the union of program intervals, idle gaps with the
   innermost host span that was open across each, device time per jitted
   program, and a span's self time.

The profiler stops recording a device's operations past a number of
events, and then the trace's programs end early or leave holes.  So a
view knows the host spans whose calls wait for their device results
(``synced``): each must hold at least one recorded program.  Where one
does not, the trace is not complete and the device-time readings return
``None`` rather than a number from part of the window.

Busy time is read from the programs and not from the ``XLA Ops`` line
beside them: over one pass of ``ours.suite-125`` on a TPU v5e the two
unions agreed to 0.1% (4.0638 s against 4.0597 s in a 7.7 s window),
and the operations inside the scans are millions of events that take
minutes to read.
"""
from __future__ import annotations

import bisect
import re
from dataclasses import dataclass, field

from bench.spans import PREFIX

MODULES_LINE = "XLA Modules"


def program_name(module: str) -> str:
    """``jit_eval_scan(1234)`` -> ``eval_scan``: the jitted function's name."""
    name = re.split(r"[(\s]", module, maxsplit=1)[0]
    name = name[4:] if name.startswith("jit_") else name
    return re.sub(r"\.\d+$", "", name)


def load(path: str) -> dict:
    """The programs each device plane ran, and the host spans."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    modules, spans = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == MODULES_LINE:
                    modules[plane.name] = [(e.start_ns, e.start_ns + e.duration_ns, program_name(e.name))
                                           for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name[len(PREFIX):], e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events if e.name.startswith(PREFIX)]
    return {"modules": modules, "spans": spans}


def union(intervals, lo: float, hi: float) -> float:
    """Length of the union of ``(start, end, ...)`` intervals, clipped to
    ``[lo, hi]``."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that no interval covers."""
    out, t = [], lo
    for s, e, *_ in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(a, b) for a, b in out if b > a]


@dataclass
class View:
    """One traced window: what the per-layer metric readers read."""

    modules: dict  # device plane -> [(start, end, program name)]
    spans: list  # [(span name, start, end)]
    counters: dict = field(default_factory=dict)
    peaks: dict = field(default_factory=dict)  # this device kind's peaks
    chips: int = 1
    synced: tuple = ()  # names of spans whose calls wait for the device

    def uncovered(self) -> int:
        """``synced`` spans in the window during which no device recorded a
        program: work the device did that the trace lost."""
        lo, hi = self.window
        progs = sorted((s, e) for mods in self.modules.values() for s, e, _ in mods)
        starts = [s for s, _ in progs]
        ends_max, m = [], float("-inf")  # running max of program ends, to test overlap by bisection
        for _, e in progs:
            m = max(m, e)
            ends_max.append(m)
        n = 0
        for name, s, e in self.spans:
            if name in self.synced and s >= lo and e <= hi:
                i = bisect.bisect_right(starts, e) - 1  # the last program that starts before the span ends
                n += i < 0 or ends_max[i] < s
        return n

    @property
    def complete(self) -> bool:
        return bool(self.modules) and self.uncovered() == 0

    @property
    def window(self) -> tuple[float, float]:
        w = [(s, e) for n, s, e in self.spans if n == "window"]
        if not w:
            raise ValueError("the trace holds no window span")
        return w[-1]

    @property
    def window_s(self) -> float:
        lo, hi = self.window
        return (hi - lo) * 1e-9

    def devices(self) -> list[str]:
        return sorted(self.modules)

    def busy_s(self) -> float:
        """Seconds in which a program ran, averaged over the devices."""
        lo, hi = self.window
        devs = self.devices()
        if not devs:
            return 0.0
        return sum(union(self.modules[d], lo, hi) for d in devs) / len(devs) * 1e-9

    def idle_share(self) -> float | None:
        """Per cent of the window in which no program ran; ``None`` where
        the trace is not complete."""
        if not self.complete:
            return None
        return 100.0 * (1.0 - self.busy_s() / self.window_s)

    def program_s(self, names) -> float | None:
        """Device seconds of the named jitted programs in the window,
        averaged over the devices; ``None`` where none of them ran or the
        trace is not complete."""
        names = set(names)
        if not self.complete or not any(m[2] in names for mods in self.modules.values() for m in mods):
            return None
        lo, hi = self.window
        devs = self.devices()
        tot = sum(union([m for m in self.modules.get(d, []) if m[2] in names], lo, hi) for d in devs)
        return tot / len(devs) * 1e-9

    def self_s(self, parents, children) -> float:
        """Seconds inside the ``parents`` spans less what ``children``
        spans inside them cover."""
        lo, hi = self.window
        par = [(max(s, lo), min(e, hi)) for n, s, e in self.spans if n in parents and e > lo and s < hi]
        kids = [(s, e) for n, s, e in self.spans if n in children]
        total = 0.0
        for s, e in par:
            total += (e - s) - union(kids, s, e)
        return total * 1e-9

    def top_programs(self, n: int = 10) -> list:
        lo, hi = self.window
        devs = self.devices()
        acc: dict = {}
        for d in devs:
            for s, e, name in self.modules.get(d, []):
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    acc[name] = acc.get(name, 0.0) + (e - s) * 1e-9 / len(devs)
        return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]

    def idle_gaps(self, n: int = 10, with_start: bool = False) -> list:
        """The longest idle stretches of the first device, each named by
        the innermost benchmark span open across its middle (and, with
        ``with_start``, the seconds from the window's start to the gap's)."""
        devs = self.devices()
        if not devs:
            return []
        lo, hi = self.window
        d = devs[0]
        out = []
        for a, b in gaps(self.modules[d], lo, hi):
            mid = (a + b) / 2
            open_ = [(s, e, name) for name, s, e in self.spans if s <= mid <= e and name != "window"]
            name = min(open_, key=lambda x: x[1] - x[0])[2] if open_ else "outside spans"
            out.append([name, (b - a) * 1e-9] + ([(a - lo) * 1e-9] if with_start else []))
        return sorted(out, key=lambda kv: -kv[1])[:n]
