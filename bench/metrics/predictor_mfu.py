"""The predictor's share of the chip's bf16 peak over the traced window:
operations of the window's evaluates and fine-tunes, counted from the
model's shapes (bench/flops.py), over the window's length, over the
peak of the chips used."""
from bench.flops import window_flops


def read(view):
    c = view.counters
    peak = view.peaks.get("bf16_flops_per_s")
    if not peak or (not c.get("eval_samples") and not c.get("train_steps")):
        return None
    ops = window_flops(c["predictor"], c, c["batch_size"])
    return 100.0 * ops / view.window_s / (peak * view.chips)
