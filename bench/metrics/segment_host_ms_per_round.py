"""The simulator's host stages in the learned rounds that the device waits
on: the device's idle time inside ``simulator.run_segment`` whose
innermost program span is event compression, lane staging, the scan's
dispatch, slicing the lane back out or decompression
(``simulator.compress``, ``.stage``, ``.dispatch``, ``.unstage``,
``.decompress``), per round.  Idle time, not the spans' length: the
``simulator.unstage`` span also holds a wait for the scan (its slices
queue behind it on the device), which is not host work.  Waits for the
device's answers (``sync.*``) are not in it."""
from bench import program_spans

SPANS = ("simulator.compress", "simulator.stage", "simulator.dispatch", "simulator.unstage",
         "simulator.decompress")
INSIDE = ("simulator.run_segment",)


def read(view):
    pv = program_spans.extend(view)
    return None if pv is None else program_spans.per(view, pv.idle_in_s(SPANS, INSIDE), "rounds")
