"""DP-SGD primitives (paper Sec. 3.2, Eq. 4-6), in pure JAX.

Three clipping granularities (DESIGN.md sec 3):

  * ``per_example``    — exact per-sample gradients via jax.vmap(jax.grad),
                         clipped individually, then averaged + noised.
                         This is what Opacus does and what the paper's 1M
                         parameter SER CNN uses.
  * ``per_microbatch`` — each gradient-accumulation microbatch is clipped
                         as a unit (virtual-batch clipping).  Used by the
                         large assigned architectures where exact
                         per-example grads are infeasible.
  * ``client_level``   — the whole client model delta is clipped + noised
                         once per round (DP-FedAvg, Geyer et al. [17]).

All return the noised mean gradient exactly as Eq. (5):

    g~ = (1/|b|) sum_i clip(g_i) + N(0, sigma^2 C^2 / |b|^2 * I-ish)

NOTE on noise scaling: Eq. (5) in the paper adds N(0, sigma^2 C^2 I) to the
*sum* before the 1/|b| factor is applied to the sum only; the standard
DP-SGD mechanism (Abadi et al.) noises the sum and then divides everything
by |b|.  We follow Abadi et al. (noise stddev sigma*C on the sum, i.e.
sigma*C/|b| on the mean) — this is also what Opacus implements, so it is
what the paper actually ran.

The per-example mechanism ships two interchangeable implementations,
selected by ``dp_path``:

  * ``"jnp"``    — reference: per-leaf norms/scales + ``noise_tree``.
  * ``"pallas"`` — the fused ``repro.kernels.dp_clip`` two-pass kernel
                   (clip + mean + Gaussian noise in the final-tile
                   epilogue), the cohort engine's production hot path.
                   Noise draws replay ``noise_tree``'s exact per-leaf
                   split order (``tree_gaussian_vector_like``) so both
                   paths agree to float tolerance; the noise stddev stays
                   a RUNTIME scalar, preserving the one-program-per-sigma-
                   sweep invariant.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp

from repro.pytree import (
    tree_gaussian_like,
    tree_global_norm,
    tree_scale,
)


DP_PATHS = ("jnp", "pallas")


def validate_dp_path(dp_path: str) -> str:
    if dp_path not in DP_PATHS:
        raise ValueError(
            f"dp_path must be one of {DP_PATHS}, got {dp_path!r}")
    return dp_path


@dataclass(frozen=True)
class DPConfig:
    clip_norm: float = 1.0        # C  (paper: C = 1)
    noise_multiplier: float = 0.0  # sigma (paper: {0.5, 1, 1.5, 2}); 0 = off
    granularity: str = "per_example"  # per_example | per_microbatch | client_level

    @property
    def enabled(self) -> bool:
        return self.noise_multiplier > 0.0 or self.clip_norm > 0.0


def clip_tree(grads, clip_norm: float):
    """Eq. (4): g <- g / max(1, ||g||_2 / C).  Returns (clipped, pre_norm)."""
    nrm = tree_global_norm(grads)
    scale = 1.0 / jnp.maximum(1.0, nrm / clip_norm)
    return tree_scale(grads, scale), nrm


def noise_tree(key, grads, stddev):
    """Add iid Gaussian noise of the given stddev to every leaf.

    ``stddev`` may be a traced scalar (the cohort engine passes the noise
    scale as a runtime argument so one compiled program serves a whole
    sigma sweep); the zero short-circuit only applies to concrete floats."""
    if isinstance(stddev, (int, float)) and stddev == 0.0:
        return grads
    noise = tree_gaussian_like(key, grads, stddev)
    return jax.tree_util.tree_map(jnp.add, grads, noise)


def per_example_grads(loss_fn: Callable, params, batch):
    """vmap(grad) over the leading batch axis of every array in ``batch``.

    ``loss_fn(params, example) -> scalar`` where ``example`` is one sample
    (no batch dim).  Returns a pytree with a leading batch axis on every
    leaf.
    """
    gfn = jax.grad(loss_fn)
    return jax.vmap(gfn, in_axes=(None, 0))(params, batch)


def dp_mean_gradient(
    loss_fn: Callable,
    params,
    batch,
    key: jax.Array,
    cfg: DPConfig,
    dp_path: str = "jnp",
    noise_stddev=None,
):
    """Per-example DP-SGD gradient (Eq. 4-6): clip each sample's grad to C,
    average, add N(0, (sigma*C/B)^2) to the mean.

    ``dp_path`` selects the implementation: ``"jnp"`` (reference) or
    ``"pallas"`` (fused clip+mean+noise kernel, see module docstring).

    ``noise_stddev`` overrides the statically derived
    ``sigma * C / B`` with a (possibly traced) runtime scalar: the cohort
    engine computes the stddev on the host once per runner and feeds it as
    a program ARGUMENT, so one compiled step serves every noise multiplier
    of a sigma sweep instead of re-tracing per sigma.

    Returns (noised_mean_grad, aux) where aux carries the mean pre-clip
    norm (useful for calibrating C) and the fraction of clipped samples.
    """
    validate_dp_path(dp_path)
    with jax.named_scope("dp_grads"):
        g_per = per_example_grads(loss_fn, params, batch)
    bsz = jax.tree_util.tree_leaves(g_per)[0].shape[0]
    stddev = (cfg.noise_multiplier * cfg.clip_norm / bsz
              if noise_stddev is None else noise_stddev)
    # mirror noise_tree's short-circuit: a CONCRETE zero stddev means no
    # noise; a traced scalar always takes the noised program.
    add_noise = not (isinstance(stddev, (int, float)) and stddev == 0.0)

    with jax.named_scope("dp_clip_noise"):
        if dp_path == "pallas":
            # fused Pallas path: flatten per-example grads to (1, B, D)
            # and run the two-pass cohort clip+mean(+noise) kernel with
            # K=1 (see repro.kernels.dp_clip).  Noise draws replay
            # noise_tree's split order so both paths agree to float
            # tolerance.
            from repro.kernels.dp_clip.ops import dp_clip_mean_noise_cohort
            from repro.pytree import (
                tree_gaussian_vector_like, tree_unflatten_from_vector)

            leaves = jax.tree_util.tree_leaves(g_per)
            flat = jnp.concatenate(
                [l.reshape(bsz, -1).astype(jnp.float32) for l in leaves],
                axis=1)
            template = jax.tree_util.tree_map(lambda l: l[0], g_per)
            if add_noise:
                z = tree_gaussian_vector_like(key, template)
                mean_flat, nrm, frac = dp_clip_mean_noise_cohort(
                    flat[None], cfg.clip_norm,
                    jnp.asarray(stddev, jnp.float32), z[None])
            else:
                mean_flat, nrm, frac = dp_clip_mean_noise_cohort(
                    flat[None], cfg.clip_norm)
            noised = tree_unflatten_from_vector(mean_flat[0], template)
            return noised, {"mean_grad_norm": nrm[0],
                            "clip_fraction": frac[0]}
        else:
            # per-sample norms over ALL leaves (flatten the non-batch dims)
            sq = sum(
                jnp.sum(jnp.square(l.astype(jnp.float32)).reshape(bsz, -1),
                        axis=1)
                for l in jax.tree_util.tree_leaves(g_per)
            )
            norms = jnp.sqrt(sq)                                   # (B,)
            scales = 1.0 / jnp.maximum(1.0, norms / cfg.clip_norm)  # (B,)
            mean = jax.tree_util.tree_map(
                lambda l: jnp.mean(
                    l * scales.reshape((bsz,) + (1,) * (l.ndim - 1)), axis=0
                ),
                g_per,
            )
            nrm = jnp.mean(norms)
            frac = jnp.mean((norms > cfg.clip_norm).astype(jnp.float32))

        noised = noise_tree(key, mean, stddev) if add_noise else mean
    return noised, {"mean_grad_norm": nrm, "clip_fraction": frac}


def dp_microbatch_gradient(grads, key, cfg: DPConfig, num_microbatches: int):
    """Per-microbatch granularity: ``grads`` is the (already-averaged)
    gradient of ONE microbatch; clip it as a unit.  Noise is added once by
    the caller after accumulation via :func:`dp_accumulate_noise`."""
    clipped, nrm = clip_tree(grads, cfg.clip_norm)
    return clipped, nrm


def dp_accumulate_noise(summed_clipped, key, cfg: DPConfig, num_units: int):
    """Finish a per-microbatch / client-level accumulation: average the
    ``num_units`` clipped units and add N(0, (sigma*C/num_units)^2)."""
    mean = tree_scale(summed_clipped, 1.0 / num_units)
    stddev = cfg.noise_multiplier * cfg.clip_norm / num_units
    return noise_tree(key, mean, stddev)


def dp_client_delta(delta, key, cfg: DPConfig):
    """Client-level DP (DP-FedAvg): clip the round's model delta to C and
    noise it before it leaves the (virtual) device."""
    clipped, nrm = clip_tree(delta, cfg.clip_norm)
    noised = noise_tree(key, clipped, cfg.noise_multiplier * cfg.clip_norm)
    return noised, nrm
