"""The predictor's operation count from its shapes, against a hand count."""
from bench_cells import ROOT  # noqa: F401  (puts the repository on the path)
from bench import flops

SMOKE = {"history": 10, "d_model": 16, "d_ff": 32, "num_layers": 1, "delta_vocab": 32}


def test_forward_per_sample_smoke_by_hand():
    # one layer per block, T=10, d=16, d_ff=32:
    #   q, k, v, o projections  4 * 10 * 16 * 16 = 10,240 multiply-adds
    #   scores and weighting    2 * 10 * 10 * 16 =  3,200
    #   SwiGLU MLP              3 * 10 * 16 * 32 = 15,360
    #   per block 28,800; two blocks 57,600
    #   head: projection 32 * 16 = 512, classes 16 * 32 = 512 -> 1,024
    #   58,624 multiply-adds = 117,248 operations
    assert flops.forward_per_sample(SMOKE) == 117_248


def test_paper_forward_and_window_count():
    paper = {"history": 10, "d_model": 64, "d_ff": 128, "num_layers": 2, "delta_vocab": 1024}
    f = flops.forward_per_sample(paper)
    assert f == 2 * (2 * 2 * (4 * 10 * 64 * 64 + 2 * 10 * 10 * 64 + 3 * 10 * 64 * 128)
                     + 2 * 64 * 64 + 64 * 1024)
    c = {"eval_samples": 100, "train_steps": 2, "lucir_steps": 1}
    assert flops.window_flops(paper, c, 256) == f * (100 + 3 * 256 * 2 + 256)


def test_peaks_are_keyed_by_device_kind():
    import pytest

    from bench.peaks import peaks

    assert peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks("cpu")
