"""Host self time of the manager per round: the benchmark's spans around
``observe`` and ``feedback``, less the trainer calls inside them.  Host
waits for the device that fall in manager code count here."""
PARENTS = ("manager.observe", "manager.feedback")
CHILDREN = ("trainer.evaluate", "trainer.train_group")


def read(view):
    rounds = view.counters.get("rounds", 0)
    if not rounds or not any(n in PARENTS for n, _, _ in view.spans):
        return None
    return view.self_s(PARENTS, CHILDREN) * 1e3 / rounds
