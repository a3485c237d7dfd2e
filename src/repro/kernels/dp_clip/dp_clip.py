"""Fused per-sample gradient clip + accumulate (+ noise) — the DP-SGD
hot spot (what Opacus spends its time on), as two tiled Pallas TPU
kernels, cohort-aware so the engine launches ONE program per cohort step.

TPU adaptation (DESIGN.md sec 3): instead of Opacus' hook-based per-layer
GPU pass, the flattened per-example grad matrix — (B, D) for a single
client, (K*B, D) for a whole stacked cohort — is swept twice with
MXU/VPU-aligned VMEM tiles:

  pass 1 (sqnorm):  grid (nB, nD); each step reduces a (TB, TD) tile to a
                    (TB, 1) column of partial sums accumulated into the
                    row norms.
  pass 2 (scale+mean+noise): grid (K, nD, nB); each step loads member
                    m's i-th (TB, TD) row tile, multiplies by the
                    per-sample scale min(1, C/||g_i||) broadcast along the
                    lanes from a (TB, 1) column, and accumulates the
                    batch-mean into the member's (1, TD) output row.  On
                    the LAST row tile an epilogue fuses the Gaussian-
                    mechanism noise add: out += stddev * z, with the
                    stddev a (1, 1) runtime scalar — sigma stays out of
                    the compiled program so one program serves the whole
                    sigma sweep.

Every block is tile-legal for Mosaic: the last two dims of each block are
either whole array dims or multiples of (8, 128).  Row vectors are (R, 1)
columns (the layout the lane broadcast consumes, so no relayout), and
the per-member rows z and out are (K, 1, Dp) with the member dim
squeezed out of the block, so a cohort of any K >= 1 tiles.  The callers
pad rows to a multiple of 8 and columns to the column tile.

Cohort padding composes with the engine's pow2 cohort padding: mask
members carry zero grads, so their rows clip to scale 1 and contribute
zero to their own (discarded) output row.

Tiles default to (128, 512) f32 = 256 KiB live VMEM per step — far under
the ~16 MiB/core budget, leaving room for double buffering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


DEFAULT_TB = 128
DEFAULT_TD = 512


def _sqnorm_kernel(flat_ref, out_ref):
    """grid (nB, nD): accumulate per-sample squared norms."""
    j = pl.program_id(1)
    tile = flat_ref[...].astype(jnp.float32)               # (TB, TD)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.sum(tile * tile, axis=1, keepdims=True)  # (TB, 1)


def _scale_mean_kernel(flat_ref, scale_ref, out_ref, *, inv_b: float):
    """grid (K, nD, nB): out[m, d] += sum_b scale[m*B+b] * flat[m*B+b, d] / B."""
    i = pl.program_id(2)
    tile = flat_ref[...].astype(jnp.float32)               # (TB, TD)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    out_ref[...] += jnp.sum(tile * scale_ref[...], axis=0,
                            keepdims=True) * inv_b         # (1, TD)


def _scale_mean_noise_kernel(flat_ref, scale_ref, z_ref, std_ref, out_ref,
                             *, inv_b: float, n_b: int):
    """_scale_mean_kernel + fused Gaussian epilogue on the last row tile:
    out[m] += std * z[m], with std a runtime (1, 1) scalar."""
    _scale_mean_kernel(flat_ref, scale_ref, out_ref, inv_b=inv_b)

    @pl.when(pl.program_id(2) == n_b - 1)
    def _noise_epilogue():
        out_ref[...] += std_ref[0, 0] * z_ref[...].astype(jnp.float32)


def sqnorms(flat, *, tb: int = DEFAULT_TB, td: int = DEFAULT_TD,
            interpret: bool = True):
    """Per-row squared norms of a tile-aligned (R, D) matrix, as an
    (R, 1) column."""
    R, D = flat.shape
    tb, td = min(tb, R), min(td, D)
    return pl.pallas_call(
        _sqnorm_kernel,
        grid=(pl.cdiv(R, tb), pl.cdiv(D, td)),
        in_specs=[pl.BlockSpec((tb, td), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((tb, 1), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R, 1), jnp.float32),
        interpret=interpret,
    )(flat)


def cohort_scale_mean(flat, scales, *, k: int, inv_b: float,
                      z=None, stddev=None,
                      tb: int = DEFAULT_TB, td: int = DEFAULT_TD,
                      interpret: bool = True):
    """Per-member clipped batch mean over a stacked cohort, one launch.

    flat:   (K*Bp, Dp) member-major per-example grads, Bp % tb == 0.
    scales: (K*Bp, 1) per-sample clip scales.
    z:      optional (K, 1, Dp) standard-normal draws; when given,
            ``stddev`` (a (1, 1) float32 array, runtime-valued) scales
            them and the kernel adds the noise in the final-tile epilogue.
    inv_b:  1 / B_real — padded rows are zero so they add nothing and no
            post-hoc rescale is needed.

    Returns (K, 1, Dp) float32 means (noised when z is given).
    """
    kb, Dp = flat.shape
    bp = kb // k
    tb, td = min(tb, bp), min(td, Dp)
    n_b = pl.cdiv(bp, tb)
    grid = (k, pl.cdiv(Dp, td), n_b)
    flat_spec = pl.BlockSpec((tb, td), lambda m, j, i: (m * n_b + i, j))
    scale_spec = pl.BlockSpec((tb, 1), lambda m, j, i: (m * n_b + i, 0))
    row_spec = pl.BlockSpec((None, 1, td), lambda m, j, i: (m, 0, j))
    out_shape = jax.ShapeDtypeStruct((k, 1, Dp), jnp.float32)
    if z is None:
        kern = functools.partial(_scale_mean_kernel, inv_b=inv_b)
        return pl.pallas_call(
            kern, grid=grid,
            in_specs=[flat_spec, scale_spec],
            out_specs=row_spec, out_shape=out_shape,
            interpret=interpret,
        )(flat, scales)
    kern = functools.partial(_scale_mean_noise_kernel, inv_b=inv_b, n_b=n_b)
    return pl.pallas_call(
        kern, grid=grid,
        in_specs=[
            flat_spec, scale_spec, row_spec,
            pl.BlockSpec((1, 1), lambda m, j, i: (0, 0)),
        ],
        out_specs=row_spec, out_shape=out_shape,
        interpret=interpret,
    )(flat, scales, z, stddev)
