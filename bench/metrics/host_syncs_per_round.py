"""Blocking device-to-host pulls per manager round: the program's
``sync.<site>`` spans (one per ``repro.obs.to_host`` call) that start in
the traced window, over the window's rounds.  Every learned round pulls,
so a window without one has lost or renamed the spans: nothing, not zero."""
from bench import program_spans


def read(view):
    pv = program_spans.extend(view)
    rounds = view.counters.get("rounds", 0)
    n = None if pv is None or not rounds else pv.program_count("sync.")
    return n / rounds if n else None
