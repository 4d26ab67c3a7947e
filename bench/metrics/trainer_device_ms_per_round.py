"""Device time of the predictor's jitted evaluate and fine-tune scans
(``Trainer``: ``eval_scan``, ``train_scan`` and their lane-batched
``*_many`` forms), per manager round."""
PROGRAMS = ("eval_scan", "train_scan", "eval_scan_many", "train_scan_many")


def read(view):
    rounds = view.counters.get("rounds", 0)
    seconds = view.program_s(PROGRAMS)
    return None if not rounds or seconds is None else seconds * 1e3 / rounds
