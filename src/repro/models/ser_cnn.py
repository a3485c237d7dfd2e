"""The paper's SER model (Sec. 3.1): a lightweight 1D CNN over
mel-spectrogram features.

  * two 1D conv blocks (64 / 128 filters, kernel 5) + GroupNorm + ReLU,
  * 1D max-pool (2) after each block,
  * dropout 0.3 / 0.4 after the conv blocks, 0.5 after the FC layer,
  * FC-128 + output layer (4 emotions).

Input: (time_frames, n_mels) mel-spectrogram patch; n_mels acts as the
channel dim of the 1D convolution over time (standard light-SER layout).

Implemented as explicit pure functions over a param dict so that
``jax.vmap(jax.grad(...))`` per-example DP-SGD (core/dp.py) works without
any framework magic.  Dropout is exposed behind ``train=True, rng=...``;
the FL simulation trains in deterministic mode (DP noise already
regularizes; per-sample dropout RNG plumbing through vmap is intentionally
avoided — see DESIGN.md §8).
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class SERConfig:
    time_frames: int = 64
    n_mels: int = 40
    channels1: int = 64
    channels2: int = 128
    kernel: int = 5
    gn_groups: int = 8
    fc_dim: int = 128
    num_classes: int = 4
    drop1: float = 0.3
    drop2: float = 0.4
    drop_fc: float = 0.5


def init(key: jax.Array, cfg: SERConfig = SERConfig()):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def conv_init(k, cin, cout, ksz):
        scale = 1.0 / jnp.sqrt(cin * ksz)
        return {
            "w": jax.random.uniform(k, (ksz, cin, cout), jnp.float32, -scale, scale),
            "b": jnp.zeros((cout,), jnp.float32),
        }

    def dense_init(k, din, dout):
        scale = 1.0 / jnp.sqrt(din)
        return {
            "w": jax.random.uniform(k, (din, dout), jnp.float32, -scale, scale),
            "b": jnp.zeros((dout,), jnp.float32),
        }

    t_after = cfg.time_frames // 4  # two maxpools of 2
    return {
        "conv1": conv_init(k1, cfg.n_mels, cfg.channels1, cfg.kernel),
        "gn1": {"scale": jnp.ones((cfg.channels1,)), "bias": jnp.zeros((cfg.channels1,))},
        "conv2": conv_init(k2, cfg.channels1, cfg.channels2, cfg.kernel),
        "gn2": {"scale": jnp.ones((cfg.channels2,)), "bias": jnp.zeros((cfg.channels2,))},
        "fc1": dense_init(k3, t_after * cfg.channels2, cfg.fc_dim),
        "out": dense_init(k4, cfg.fc_dim, cfg.num_classes),
    }


def _conv1d(x, p):
    """x: (T, Cin) -> (T, Cout), SAME padding."""
    y = jax.lax.conv_general_dilated(
        x[None],                       # (1, T, Cin)
        p["w"],                        # (K, Cin, Cout)
        window_strides=(1,),
        padding="SAME",
        dimension_numbers=("NWC", "WIO", "NWC"),
    )[0]
    return y + p["b"]


def _group_norm(x, p, groups):
    """x: (T, C) grouped over channels."""
    t, c = x.shape
    xg = x.reshape(t, groups, c // groups)
    mean = xg.mean(axis=(0, 2), keepdims=True)
    var = xg.var(axis=(0, 2), keepdims=True)
    xg = (xg - mean) * jax.lax.rsqrt(var + 1e-5)
    return xg.reshape(t, c) * p["scale"] + p["bias"]


def _maxpool2(x):
    t, c = x.shape
    return x.reshape(t // 2, 2, c).max(axis=1)


def _dropout(x, rate, rng, train):
    if not train or rate == 0.0 or rng is None:
        return x
    keep = jax.random.bernoulli(rng, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def apply(params, x, cfg: SERConfig = SERConfig(), train: bool = False, rng=None):
    """x: (time_frames, n_mels) -> logits (num_classes,)."""
    rngs = jax.random.split(rng, 3) if (train and rng is not None) else (None,) * 3
    h = _conv1d(x, params["conv1"])
    h = _group_norm(h, params["gn1"], cfg.gn_groups)
    h = jax.nn.relu(h)
    h = _maxpool2(h)
    h = _dropout(h, cfg.drop1, rngs[0], train)

    h = _conv1d(h, params["conv2"])
    h = _group_norm(h, params["gn2"], cfg.gn_groups)
    h = jax.nn.relu(h)
    h = _maxpool2(h)
    h = _dropout(h, cfg.drop2, rngs[1], train)

    h = h.reshape(-1)
    h = jax.nn.relu(h @ params["fc1"]["w"] + params["fc1"]["b"])
    h = _dropout(h, cfg.drop_fc, rngs[2], train)
    return h @ params["out"]["w"] + params["out"]["b"]


def loss_fn(params, example, cfg: SERConfig = SERConfig()):
    """Cross-entropy loss for ONE example (paper Eq. 2); vmap-able."""
    logits = apply(params, example["x"], cfg)
    return -jax.nn.log_softmax(logits)[example["y"]]


def make_accuracy_fn(cfg: SERConfig = SERConfig(), batch: int = 512):
    @jax.jit
    def eval_accuracy(params, data):
        logits = jax.vmap(lambda x: apply(params, x, cfg))(data["x"])
        return jnp.mean((jnp.argmax(logits, -1) == data["y"]).astype(jnp.float32))

    return eval_accuracy


def param_count(params) -> int:
    return sum(l.size for l in jax.tree_util.tree_leaves(params))
