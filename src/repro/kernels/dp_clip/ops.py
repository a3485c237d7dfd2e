"""jit'd wrappers around the fused DP clip(+noise) kernels."""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.common import interpret_mode
from repro.kernels.dp_clip.dp_clip import (
    DEFAULT_TB, DEFAULT_TD, cohort_scale_mean, sqnorms)


def _pad_to(x, mult, axis):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@partial(jax.jit, static_argnames=("clip_norm",))
def dp_clip_mean_noise_cohort(g, clip_norm: float, noise_stddev=None, z=None):
    """g: (K, B, D) stacked per-example grads for a whole cohort ->
    (means (K, D), mean_norms (K,), clip_fractions (K,)) in ONE launch
    per pass over the member-major (K*Bp, Dp) matrix.

    When ``z`` ((K, D) standard-normal draws) is given, ``noise_stddev``
    (runtime float32 scalar — NOT baked into the compiled program) scales
    it inside the kernel's final-tile epilogue: means[m] += stddev * z[m].

    Inputs are zero-padded to tile multiples: padded rows have norm 0 and
    scale 1 so they contribute nothing; the member mean divides by the
    REAL B inside the kernel (inv_b), so no post-hoc rescale is needed.
    Zero-grad mask members (engine cohort padding) likewise produce a
    harmless all-zero mean row.
    """
    K, B, D = g.shape
    interp = interpret_mode()
    # row tile: a multiple of 8 (the sublane tile), at most DEFAULT_TB
    tb, td = min(DEFAULT_TB, -(-B // 8) * 8), min(DEFAULT_TD, D)
    gp = _pad_to(_pad_to(g, tb, 1), td, 2)          # (K, Bp, Dp)
    Bp, Dp = gp.shape[1], gp.shape[2]
    flat = gp.reshape(K * Bp, Dp)
    norms = jnp.sqrt(sqnorms(flat, tb=tb, td=td, interpret=interp))  # (K*Bp, 1)
    scales = 1.0 / jnp.maximum(1.0, norms / clip_norm)
    if z is not None:
        z = _pad_to(z.astype(jnp.float32), td, 1).reshape(K, 1, Dp)
        stddev = jnp.asarray(noise_stddev, jnp.float32).reshape(1, 1)
    else:
        stddev = None
    means = cohort_scale_mean(flat, scales, k=K, inv_b=1.0 / B,
                              z=z, stddev=stddev,
                              tb=tb, td=td, interpret=interp)
    norms = norms.reshape(K, Bp)[:, :B]
    return (means.reshape(K, Dp)[:, :D], jnp.mean(norms, axis=1),
            jnp.mean((norms > clip_norm).astype(jnp.float32), axis=1))


@partial(jax.jit, static_argnames=("clip_norm",))
def dp_clip_mean_flat(flat, clip_norm: float):
    """flat: (B, D) per-example grads -> (mean_clipped (D,), mean_norm,
    clip_fraction).  Single-member (K=1) view of the cohort op."""
    means, nrms, fracs = dp_clip_mean_noise_cohort(flat[None], clip_norm)
    return means[0], nrms[0], fracs[0]
