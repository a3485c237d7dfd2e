"""Operations and bytes of the SER CNN's DP-SGD step and of the fused
``dp_clip`` kernel, from the configuration's shapes alone.

A multiply-add counts as two operations.  The backward pass of a layer
costs twice its forward pass (the gradient with respect to the input and
with respect to the weights), so forward plus backward is three times
the forward.  Elementwise work (GroupNorm, ReLU, pooling, softmax) is
left out: it is under 1% of the convolutions' and dense layers' count.

At the paper's widths (64x40 input, 64/128 filters of width 5, FC-128):
4.78 MFLOP forward and 14.3 MFLOP forward plus backward per example,
1.84 GFLOP per DP step of B=128, 317,124 parameters, and 162 MB of
float32 per-example gradients per step.
"""
from __future__ import annotations


def forward_flops(m: dict) -> int:
    """Forward operations for one example."""
    t, k = m["time_frames"], m["kernel"]
    c0, c1, c2 = m["n_mels"], m["channels1"], m["channels2"]
    conv1 = 2 * t * k * c0 * c1
    conv2 = 2 * (t // 2) * k * c1 * c2
    fc1 = 2 * (t // 4) * c2 * m["fc_dim"]
    out = 2 * m["fc_dim"] * m["num_classes"]
    return conv1 + conv2 + fc1 + out


def train_flops_per_example(m: dict) -> int:
    """Forward plus backward operations for one example."""
    return 3 * forward_flops(m)


def dp_step_flops(m: dict, batch: int) -> int:
    """One DP-SGD step: every example's forward and backward pass."""
    return batch * train_flops_per_example(m)


def param_count(m: dict) -> int:
    t, k = m["time_frames"], m["kernel"]
    c0, c1, c2, f = m["n_mels"], m["channels1"], m["channels2"], m["fc_dim"]
    return (k * c0 * c1 + c1 + 2 * c1          # conv1 + GroupNorm 1
            + k * c1 * c2 + c2 + 2 * c2        # conv2 + GroupNorm 2
            + (t // 4) * c2 * f + f            # FC
            + f * m["num_classes"] + m["num_classes"])


def per_example_grad_bytes(m: dict, batch: int) -> int:
    """The float32 (B, D) per-example gradient matrix of one step."""
    return 4 * batch * param_count(m)


def _ceil(x: int, mult: int) -> int:
    return -(-x // mult) * mult


def dp_clip_cost(k: int, batch: int, d: int, tb: int = 128,
                 td: int = 512) -> tuple:
    """``(flops, bytes)`` of one fused clip+mean+noise call over a cohort
    of ``k`` members, each with a (batch, d) float32 gradient matrix.

    The call pads rows to the row tile and columns to the column tile,
    then makes two passes over the (k * Bp, Dp) matrix: one for the
    per-row squared norms, one that scales, averages and adds the noise
    rows.  Bytes: the matrix read twice, the (k * Bp,) norms written and
    read back as scales, the (k, Dp) noise read and the (k, Dp) means
    written.  Operations: a multiply-add per element in each pass and
    a multiply-add per mean element for the noise."""
    tb = min(tb, _ceil(batch, 8))
    td = min(td, d)
    bp, dp = _ceil(batch, tb), _ceil(d, td)
    rows = k * bp
    flops = 2 * rows * dp + 2 * rows * dp + 2 * k * dp
    nbytes = 4 * (2 * rows * dp + 2 * rows + 2 * k * dp)
    return flops, nbytes
