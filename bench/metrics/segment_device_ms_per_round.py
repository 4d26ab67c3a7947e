"""Device time of the simulator's jitted segment programs in the learned
rounds (the event scan ``run_events`` that ``run_segment`` drives, and the
prefetch staging ``apply_prefetch``), per manager round."""
PROGRAMS = ("run_events", "run_events_lanes", "apply_prefetch")


def read(view):
    rounds = view.counters.get("rounds", 0)
    seconds = view.program_s(PROGRAMS)
    return None if not rounds or seconds is None else seconds * 1e3 / rounds
