"""The program's own spans in a traced window, beside the benchmark's.

The program names its host stages and its device-to-host pulls with
spans called ``repro:<name>`` (``repro.obs``): ``runtime.round``,
``trainer.stage``, ``simulator.compress``, ``sync.<site>`` and the like.
:mod:`bench.reduce` reads a trace's device programs and the benchmark's
own ``bench:`` spans and leaves these out, so every reader of it reads
what it read before.  This module reads the same file's program spans
into a list of their own:

* :func:`extend` finds the traced window's file again (by its
  ``bench:window`` span) and returns the view as a :class:`ProgramView`;
* :class:`ProgramView` adds the union of named program spans, the count
  of spans by prefix, idle gaps named by the innermost span of either
  list, and the window's idle time split by program span.

A program without these spans (one older than them) gives a view with
none, and every reader of them reads ``None``, never zero.

    python3 -m bench.program_spans [TRACE.xplane.pb]

prints that split for the newest traced window (or the file given).
"""
from __future__ import annotations

import functools
import json
import sys
import warnings
from dataclasses import dataclass, field
from pathlib import Path

from bench import reduce
from bench.run import TRACE_DIR
from bench.spans import PREFIX as BENCH_PREFIX

PREFIX = "repro:"  # the program's span prefix (repro.obs.PREFIX), kept here so an older program still loads
OUTSIDE = "outside program spans"


@functools.lru_cache(maxsize=8)
def _read(path: str, stamp) -> tuple:
    """``(window, spans)`` of one trace file: its last ``bench:window``
    interval and its program spans ``(name, start, end, ids)``."""
    from jax.profiler import ProfileData

    window, spans = None, []
    with warnings.catch_warnings():  # the profiler's stat type has no __module__
        warnings.simplefilter("ignore", DeprecationWarning)
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        spans.append((e.name[len(PREFIX):], e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
                    elif e.name == BENCH_PREFIX + "window":
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    return window, tuple(sorted(spans, key=lambda s: s[1]))


def _stamped(path: Path) -> tuple:
    st = path.stat()
    return _read(str(path), (st.st_mtime_ns, st.st_size))


def load(path) -> list:
    """The program spans of one trace file: ``[(name, start, end, ids)]``."""
    return list(_stamped(Path(path))[1])


def find(window, trace_dir: Path | None = None) -> list | None:
    """The program spans of the traced file under ``trace_dir`` (the
    benchmark's traced windows by default) whose window is ``window``,
    newest files first; ``None`` where no file has that window."""
    files = Path(TRACE_DIR if trace_dir is None else trace_dir).rglob("*.xplane.pb")
    for path in sorted(files, key=lambda p: p.stat().st_mtime, reverse=True):
        w, spans = _stamped(path)
        if w == tuple(window):
            return list(spans)
    return None


@dataclass
class ProgramView(reduce.View):
    """A traced window with the program's spans beside the benchmark's."""

    program_spans: list = field(default_factory=list)  # [(name, start, end, ids)]

    def _in_window(self) -> list:
        lo, hi = self.window
        return [p for p in self.program_spans if p[2] > lo and p[1] < hi]

    def program_union_s(self, names, inside=()) -> float | None:
        """Seconds of the window that the named program spans cover (a
        union: nested or overlapping spans count once), counting only
        what lies inside ``inside`` spans where those are given; ``None``
        where none of the named spans (or none of ``inside``) is there."""
        lo, hi = self.window
        spans = self._in_window()
        kids = [(s, e) for name, s, e, _ in spans if name in set(names)]
        parents = [(s, e) for name, s, e, _ in spans if name in set(inside)]
        if not kids or (inside and not parents):
            return None
        # the stretches the parents cover: what the gaps between them leave
        within = reduce.gaps(reduce.gaps(parents, lo, hi), lo, hi) if inside else [(lo, hi)]
        return sum(reduce.union(kids, a, b) for a, b in within) * 1e-9

    def program_count(self, prefix: str) -> int | None:
        """Program spans that start in the window and whose name starts
        with ``prefix``; ``None`` where the window holds no program span
        at all (a program without them), so a true 0 still reads 0."""
        lo, hi = self.window
        starts = [p[0] for p in self.program_spans if lo <= p[1] < hi]
        return sum(n.startswith(prefix) for n in starts) if starts else None

    @staticmethod
    def _innermost(t: float, spans) -> str | None:
        """The name of the shortest span open at ``t``."""
        open_ = [(e - s, name) for name, s, e, *_ in spans if s <= t <= e]
        return min(open_)[1] if open_ else None

    def idle_gaps(self, n: int = 10, with_start: bool = False) -> list:
        """As :meth:`reduce.View.idle_gaps`, each gap named by the innermost
        span of either list open across its middle."""
        both = self.spans + [(name, s, e) for name, s, e, _ids in self._in_window()]
        return reduce.View(self.modules, both).idle_gaps(n, with_start)

    def _idle_pieces(self):
        """``(start, end, name)`` for each stretch of the first device's
        idle time in the window, cut at every program span's ends and named
        by the innermost program span open across it (``OUTSIDE`` where
        none is)."""
        devs = self.devices()
        if not devs:
            return
        lo, hi = self.window
        spans = self._in_window()
        for a, b in reduce.gaps(self.modules[devs[0]], lo, hi):
            cuts = sorted({a, b} | {t for _, s, e, _ids in spans for t in (s, e) if a < t < b})
            for x, y in zip(cuts, cuts[1:]):
                yield x, y, self._innermost((x + y) / 2, spans) or OUTSIDE

    def idle_split(self) -> dict:
        """Seconds of the first device's idle time in the window, by the
        innermost program span open at each instant (``OUTSIDE`` where
        none is)."""
        out: dict = {}
        for x, y, name in self._idle_pieces():
            out[name] = out.get(name, 0.0) + (y - x) * 1e-9
        return out

    def idle_in_s(self, names, inside=()) -> float | None:
        """Seconds of the first device's idle time spent in the named host
        stages: each instant whose innermost program span is one of
        ``names``, counting only what lies inside ``inside`` spans where
        those are given.  A span that also holds a wait for the device
        counts only where the device was idle.  ``None`` where the trace
        is not complete or none of the named spans (or none of ``inside``)
        is in the window."""
        spans, names, inside = self._in_window(), set(names), set(inside)
        if (not self.complete or not any(p[0] in names for p in spans)
                or (inside and not any(p[0] in inside for p in spans))):
            return None
        parents = [(s, e) for name, s, e, _ids in spans if name in inside]
        total = 0.0
        for x, y, name in self._idle_pieces():  # cut at every span's ends: a piece is inside a parent or not
            if name in names and (not inside or any(s <= (x + y) / 2 <= e for s, e in parents)):
                total += y - x
        return total * 1e-9


def extend(view) -> ProgramView | None:
    """``view`` with the program spans of its traced window; ``None``
    where that window's file is not there to read."""
    if isinstance(view, ProgramView):
        return view
    spans = find(view.window)
    if spans is None:
        return None
    return ProgramView(view.modules, view.spans, view.counters, view.peaks, view.chips, view.synced, spans)


def per(view, seconds: float | None, key: str, scale: float = 1e3) -> float | None:
    """``seconds`` x ``scale`` per ``view.counters[key]`` (rounds, calls);
    ``None`` where either is missing."""
    n = view.counters.get(key, 0)
    return None if seconds is None or not n else seconds * scale / n


def summary(view: ProgramView) -> dict:
    """The window's idle time by program span, its longest idle gaps so
    named, the reruns of diverged periodic segments, and each program
    span's union and count, for ``PERF.md``."""
    idle = view.idle_split()
    total = sum(idle.values())
    share = {k: v / total for k, v in sorted(idle.items(), key=lambda kv: -kv[1])} if total else {}
    names = sorted({p[0] for p in view._in_window()})
    return {"window_s": view.window_s, "busy_s": view.busy_s(), "idle_s": total, "idle_share_by_span": share,
            "idle_in_sync_share": sum(v for k, v in share.items() if k.startswith("sync.")),
            "idle_gaps": view.idle_gaps(), "reruns": view.program_count("simulator.rerun"),
            "span_union_s": {k: view.program_union_s([k]) for k in names},
            "span_count": {k: sum(p[0] == k for p in view._in_window()) for k in names}}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    paths = [Path(argv[0])] if argv else sorted(TRACE_DIR.rglob("*.xplane.pb"), key=lambda p: p.stat().st_mtime)
    if not paths:
        print(f"bench.program_spans: no trace under {TRACE_DIR}", file=sys.stderr)
        return 1
    raw = reduce.load(paths[-1])
    view = ProgramView(raw["modules"], raw["spans"], program_spans=load(paths[-1]))
    print(json.dumps({"trace": str(paths[-1]), **summary(view)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
