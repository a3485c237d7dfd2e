"""The SER CNN's conv weight gradient is an im2col contraction (the custom
VJP of ``ser_cnn._conv``), not autodiff's convolution.

* Per-example gradients (``vmap(grad)``) match plain-conv autodiff per
  example and per leaf, at both conv layers' widths, over the batch
  sizes of the tests and the paper, and on an input that is non-zero
  only in the frames SAME padding reaches.
* The forward, ``apply`` and ``make_accuracy_fn``, is the plain conv bit
  for bit.
* One per-example DP step lowers with no grouped convolution and one
  batched HIGHEST-precision weight-gradient ``dot_general`` per conv
  layer: autodiff's weight gradient under ``vmap`` is a convolution with
  ``feature_group_count`` = B, which the TPU compiler runs on the VPU.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.dp import DPConfig, dp_mean_gradient
from repro.models import ser_cnn

# (T, Cin, Cout) of conv1 and conv2 at the paper's widths
LAYERS = {"conv1": (64, 40, 64), "conv2": (32, 64, 128)}
KERNEL = 5


def _plain_conv1d(x, p):
    return ser_cnn._conv_same(x, p["w"]) + p["b"]


def _inputs(layer, b, where):
    t, cin, cout = LAYERS[layer]
    kx, kw, kb, kr = jax.random.split(jax.random.PRNGKey(7), 4)
    x = jax.random.normal(kx, (b, t, cin), jnp.float32)
    if where == "edges":
        frames = jnp.zeros((t,)).at[:2].set(1.0).at[-2:].set(1.0)
        x = x * frames[None, :, None]
    p = {"w": jax.random.normal(kw, (KERNEL, cin, cout)) / np.sqrt(KERNEL * cin),
         "b": jax.random.normal(kb, (cout,))}
    r = jax.random.normal(kr, (t, cout))
    return x, p, r


def _per_example_grads(conv1d, x, p, r):
    def loss(p, x):
        return jnp.sum(jnp.tanh(conv1d(x, p)) * r)
    return jax.jit(jax.vmap(jax.grad(loss, argnums=(0, 1)),
                            in_axes=(None, 0)))(p, x)


@pytest.mark.parametrize("where", ["dense", "edges"])
@pytest.mark.parametrize("b", [1, 16, 128])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_per_example_grads_match_plain_conv(layer, b, where):
    x, p, r = _inputs(layer, b, where)
    got = _per_example_grads(ser_cnn._conv1d, x, p, r)
    want = _per_example_grads(_plain_conv1d, x, p, r)
    names = ("w", "b", "x")
    for name, g, w in zip(names, jax.tree_util.tree_leaves(got),
                          jax.tree_util.tree_leaves(want)):
        g = np.asarray(g).reshape(b, -1)
        w = np.asarray(w).reshape(b, -1)
        scale = np.abs(w).max(axis=1)
        assert (scale > 0).all(), name
        rel = np.abs(g - w).max(axis=1) / scale
        assert rel.max() <= 1e-6, (name, rel.max())


def test_forward_is_the_plain_conv(monkeypatch):
    cfg = ser_cnn.SERConfig()
    params = ser_cnn.init(jax.random.PRNGKey(0), cfg)
    kx, ky = jax.random.split(jax.random.PRNGKey(1))
    data = {"x": jax.random.normal(kx, (64, cfg.time_frames, cfg.n_mels)),
            "y": jax.random.randint(ky, (64,), 0, cfg.num_classes)}

    def outputs():
        logits = jax.jit(jax.vmap(lambda x: ser_cnn.apply(params, x, cfg)))(
            data["x"])
        return logits, ser_cnn.make_accuracy_fn(cfg)(params, data)

    logits, acc = outputs()
    monkeypatch.setattr(ser_cnn, "_conv", ser_cnn._conv_same)
    plain_logits, plain_acc = outputs()
    np.testing.assert_array_equal(np.asarray(logits), np.asarray(plain_logits))
    assert float(acc) == float(plain_acc)


_TINY = ser_cnn.SERConfig(channels1=8, channels2=16, fc_dim=32,
                          time_frames=12, n_mels=12)


def _dp_step_text(cfg, dp_path, b):
    params = ser_cnn.init(jax.random.PRNGKey(0), cfg)
    batch = {"x": jnp.zeros((b, cfg.time_frames, cfg.n_mels)),
             "y": jnp.zeros((b,), jnp.int32)}

    def step(p, batch, key):
        return dp_mean_gradient(lambda q, ex: ser_cnn.loss_fn(q, ex, cfg),
                                p, batch, key, DPConfig(1.0, 1.0), dp_path)
    return jax.jit(step).lower(params, batch, jax.random.PRNGKey(1)).as_text()


def _grouped_convs(text):
    convs = [l for l in text.splitlines() if "stablehlo.convolution" in l]
    groups = [max(int(n) for n in re.findall(
        r"(?:feature|batch)_group_count = (\d+)", l)) for l in convs]
    return len(convs), sum(g > 1 for g in groups)


def _weight_grad_dots(text):
    """Result shapes of the batched HIGHEST-precision dot_generals."""
    return sorted(
        m.group(1) for m in re.finditer(
            r"stablehlo\.dot_general .*batching_dims.*"
            r"precision = \[HIGHEST, HIGHEST\].*-> tensor<([0-9x]+)xf32>",
            text))


@pytest.mark.parametrize("dp_path,cfg,b", [
    ("jnp", ser_cnn.SERConfig(), 128),
    ("pallas", _TINY, 16),
])
def test_dp_step_has_no_grouped_conv(monkeypatch, dp_path, cfg, b):
    text = _dp_step_text(cfg, dp_path, b)
    n_conv, n_grouped = _grouped_convs(text)
    assert n_conv >= 2 and n_grouped == 0, (n_conv, n_grouped)
    k = cfg.kernel
    assert _weight_grad_dots(text) == sorted([
        f"{b}x{k}x{cfg.n_mels}x{cfg.channels1}",
        f"{b}x{k}x{cfg.channels1}x{cfg.channels2}"])

    # the control: plain autodiff lowers both weight gradients to
    # grouped convolutions, which this audit sees
    monkeypatch.setattr(ser_cnn, "_conv", ser_cnn._conv_same)
    plain = _dp_step_text(cfg, dp_path, b)
    assert _grouped_convs(plain)[1] == 2
    assert _weight_grad_dots(plain) == []
