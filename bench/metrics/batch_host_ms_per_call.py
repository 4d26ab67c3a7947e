"""The host stages of a policy sweep's ``run_batch`` call that the device
waits on: the device's idle time inside ``simulator.run_batch`` whose
innermost program span is building and staging the lanes' states, event
compression, the scan's dispatch or decompression (``simulator.stage``,
``.compress``, ``.dispatch``, ``.unstage``, ``.decompress``), per call.
Waits for the device's answers (``sync.*``) are not in it."""
from bench import program_spans

SPANS = ("simulator.compress", "simulator.stage", "simulator.dispatch", "simulator.unstage",
         "simulator.decompress")
INSIDE = ("simulator.run_batch",)


def read(view):
    pv = program_spans.extend(view)
    return None if pv is None else program_spans.per(view, pv.idle_in_s(SPANS, INSIDE), "calls")
