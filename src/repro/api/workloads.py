"""Workload registry: the pluggable model family behind a testbed.

``build_testbed`` used to hard-wire the paper's SER CNN; a *workload*
packages everything the testbed needs from a model family —

  * ``init(key, model_cfg) -> params``
  * ``loss(model_cfg) -> loss_fn(params, example) -> scalar``
    (per-example, vmap-able: both the legacy per-client loop and the
    compiled cohort step drive it through ``jax.vmap(jax.grad(...))``)
  * ``accuracy(model_cfg) -> accuracy_fn(params, data) -> scalar``

— keyed by ``TestbedConfig.workload``.  Registering a new name is all it
takes for arch-zoo models (``repro.configs``) or ad-hoc baselines to run
through the same ``ExperimentSpec``/``Session`` machinery as the paper's
CNN.

The loss and accuracy closures are memoized per (workload, model_cfg):
jitted steps key on the loss OBJECT (static arg / engine step cache), so
handing every testbed built from the same config the same closure is what
lets repeated runs and sweeps reuse compiled programs instead of
re-tracing per ``build_testbed`` call.

Built-ins:

  * ``"ser_cnn"``    — the paper's 1D-CNN speech-emotion model
    (:mod:`repro.models.ser_cnn`); the default.
  * ``"ser_linear"`` — multinomial logistic regression over the same
    mel-spectrogram patches: a deliberately tiny convex baseline whose
    per-step cost is negligible, used by the sweep smoke tests/CI to
    exercise the Session machinery without paying CNN compiles.
"""
from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import Callable

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class Workload:
    name: str
    init: Callable                 # (key, model_cfg) -> params
    loss: Callable                 # (model_cfg) -> loss_fn
    accuracy: Callable             # (model_cfg) -> accuracy_fn

    # memoized closure accessors — identity-stable per model_cfg
    def shared_loss(self, model_cfg):
        return _shared_closure(self.name, "loss", model_cfg)

    def shared_accuracy(self, model_cfg):
        return _shared_closure(self.name, "accuracy", model_cfg)


_REGISTRY: dict = {}


@lru_cache(maxsize=None)
def _shared_closure(workload: str, kind: str, model_cfg):
    wl = get_workload(workload)
    return (wl.loss if kind == "loss" else wl.accuracy)(model_cfg)


def register_workload(name: str, *, init: Callable, loss: Callable,
                      accuracy: Callable, overwrite: bool = False) -> Workload:
    """Register a model family under ``name`` (see module docstring for
    the three factory signatures).  Re-registering an existing name is an
    error unless ``overwrite=True`` — silent replacement would detach the
    memoized closures live testbeds already hold."""
    if name in _REGISTRY and not overwrite:
        raise ValueError(
            f"workload {name!r} is already registered "
            "(pass overwrite=True to replace it)")
    wl = Workload(name=name, init=init, loss=loss, accuracy=accuracy)
    _REGISTRY[name] = wl
    if overwrite:
        _shared_closure.cache_clear()
    return wl


def get_workload(name: str) -> Workload:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown workload: {name!r} "
            f"(registered: {', '.join(sorted(_REGISTRY))})") from None


def workload_names() -> tuple:
    return tuple(sorted(_REGISTRY))


# ---------------------------------------------------------------------------
# built-in: the paper's SER CNN
# ---------------------------------------------------------------------------

def _register_builtins():
    from repro.models import ser_cnn

    register_workload(
        "ser_cnn",
        init=ser_cnn.init,
        loss=lambda cfg: partial(ser_cnn.loss_fn, cfg=cfg),
        accuracy=ser_cnn.make_accuracy_fn,
    )

    # tiny convex baseline over the same (time_frames, n_mels) patches
    def _linear_init(key, cfg):
        d = cfg.time_frames * cfg.n_mels
        scale = 1.0 / jnp.sqrt(d)
        return {
            "w": jax.random.uniform(key, (d, cfg.num_classes), jnp.float32,
                                    -scale, scale),
            "b": jnp.zeros((cfg.num_classes,), jnp.float32),
        }

    def _linear_logits(params, x):
        return x.reshape(-1) @ params["w"] + params["b"]

    def _linear_loss(cfg):
        def loss_fn(params, example):
            logits = _linear_logits(params, example["x"])
            return -jax.nn.log_softmax(logits)[example["y"]]
        return loss_fn

    def _linear_accuracy(cfg):
        @jax.jit
        def eval_accuracy(params, data):
            logits = jax.vmap(lambda x: _linear_logits(params, x))(data["x"])
            return jnp.mean(
                (jnp.argmax(logits, -1) == data["y"]).astype(jnp.float32))
        return eval_accuracy

    register_workload(
        "ser_linear",
        init=_linear_init,
        loss=_linear_loss,
        accuracy=_linear_accuracy,
    )


_register_builtins()
