"""Operations the page predictor needs, from its shapes.

One sample is ``history`` positions through two Transformer stacks (the
regular and the irregular block), each of ``num_layers`` pre-norm layers
with causal self-attention and a SwiGLU MLP, then the last position of
each through the gated projection and the cosine classifier over the
delta vocabulary.  Counted are the multiply-adds of the matrix products,
two operations each; attention scores and their weighting are counted
over the full ``history x history`` square.  Norms, softmax, embedding
gathers and the optimizer are elementwise and left out.  A training
step is the forward pass and its backward pass (three forwards), and one
more forward through the previous parameters where LUCIR distillation is
on.
"""
from __future__ import annotations


def forward_per_sample(p: dict) -> int:
    T, d, ff, L = p["history"], p["d_model"], p["d_ff"], p["num_layers"]
    layer = 4 * T * d * d + 2 * T * T * d + 3 * T * d * ff
    head = 2 * d * d + d * p["delta_vocab"]
    return 2 * (2 * L * layer + head)


def window_flops(p: dict, counters: dict, batch_size: int) -> int:
    """Evaluates (one forward per sample) and fine-tune steps of the window."""
    f = forward_per_sample(p)
    return f * (counters["eval_samples"] + 3 * batch_size * counters["train_steps"]
                + batch_size * counters["lucir_steps"])
