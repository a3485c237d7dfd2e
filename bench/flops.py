"""Operations and bytes of a DP-SGD step and of the fused ``dp_clip``
kernel, from the configuration's shapes alone.

The model's own counts (forward plus backward operations per example,
parameters) come from its family, ``bench/families/<family>.py``; this
module adds what the DP machinery, shared by every family, does with
them.
"""
from __future__ import annotations


def dp_step_flops(family, m: dict, batch: int) -> int:
    """One DP-SGD step: every example's forward and backward pass."""
    return batch * family.train_flops_per_example(m)


def per_example_grad_bytes(family, m: dict, batch: int) -> int:
    """The float32 (B, D) per-example gradient matrix of one step."""
    return 4 * batch * family.param_count(m)


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
