"""Device time of ``run_batch``'s event scan (``run_events``) per
compressed event and lane: the scan's length is the compressed event
count, and every lane steps through every event."""
PROGRAMS = ("run_events", "run_events_lanes")


def read(view):
    events = view.counters.get("lane_events", 0)
    seconds = view.program_s(PROGRAMS)
    return None if not events or seconds is None else seconds * 1e9 / events
