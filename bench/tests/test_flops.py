"""Operation and byte counts against the figures worked out by hand for
the paper's SER CNN (64x40 input, 64/128 filters of width 5, FC-128)."""
import pytest

from bench import flops

PAPER = {"time_frames": 64, "n_mels": 40, "channels1": 64, "channels2": 128,
         "kernel": 5, "gn_groups": 8, "fc_dim": 128, "num_classes": 4}


def test_param_count_matches_the_paper_model():
    assert flops.param_count(PAPER) == 317_124


def test_forward_flops_by_layer():
    # conv1 2*64*5*40*64, conv2 2*32*5*64*128, fc1 2*2048*128, out 2*128*4
    assert flops.forward_flops(PAPER) == (1_638_400 + 2_621_440 + 524_288
                                          + 1_024)


@pytest.mark.parametrize("what, value, expect, rel", [
    ("per example, forward and backward", lambda: flops.train_flops_per_example(PAPER), 14e6, 0.03),
    ("per DP step of B=128", lambda: flops.dp_step_flops(PAPER, 128), 1.8e9, 0.03),
    ("per-example grads of B=128", lambda: flops.per_example_grad_bytes(PAPER, 128), 162e6, 0.01),
])
def test_matches_hand_figures(what, value, expect, rel):
    assert value() == pytest.approx(expect, rel=rel), what


def test_dp_clip_cost_reads_the_padded_matrix_twice():
    d = flops.param_count(PAPER)
    fl, nbytes = flops.dp_clip_cost(2, 128, d)
    dp = -(-d // 512) * 512
    assert nbytes == 4 * (2 * 2 * 128 * dp + 2 * 2 * 128 + 2 * 2 * dp)
    assert fl == 4 * 2 * 128 * dp + 2 * 2 * dp
    # rows pad to a multiple of 8: B=16 stays 16, B=12 becomes 16
    assert flops.dp_clip_cost(1, 12, 512) == flops.dp_clip_cost(1, 16, 512)
    # memory bound: under one operation per byte
    assert fl / nbytes < 1.0
