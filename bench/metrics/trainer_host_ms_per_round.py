"""The trainer's host stages that the device waits on: the device's idle
time whose innermost program span is staging a round's work
(``trainer.stage``: schedules, padding, puts and lane stacking) or the
scans' dispatch (``trainer.dispatch``), for every evaluate and
fine-tune, per round."""
from bench import program_spans

SPANS = ("trainer.stage", "trainer.dispatch")


def read(view):
    pv = program_spans.extend(view)
    return None if pv is None else program_spans.per(view, pv.idle_in_s(SPANS), "rounds")
