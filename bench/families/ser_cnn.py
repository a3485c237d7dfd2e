"""The ``ser_cnn`` model family: the paper's SER 1D-CNN over a synthetic
speech-emotion corpus (arXiv:2505.07041, Sec. 3.1).

For the harness (``bench/run.py``), ``testbed_fields`` maps a
configuration's ``data`` and ``model`` onto the program's
``TestbedConfig``.  For the plain reference (``bench/reference.py``),
written from the semantics and importing nothing of the program:

* the synthetic SER corpus (shared low-rank class basis, speaker fields,
  Gaussian noise, label flips), built from the configuration's ``data``;
* the SER 1D-CNN (two conv+GroupNorm+ReLU+maxpool blocks, FC, output),
  its initial weights and its per-example cross-entropy loss.

For the readers (``bench/metrics``), the operations and parameters of
the CNN from its shapes alone.  A multiply-add counts as two
operations.  The backward pass of a layer costs twice its forward pass
(the gradient with respect to the input and with respect to the
weights), so forward plus backward is three times the forward.
Elementwise work (GroupNorm, ReLU, pooling, softmax) is left out: it is
under 1% of the convolutions' and dense layers' count.  At the paper's
widths (64x40 input, 64/128 filters of width 5, FC-128): 4.78 MFLOP
forward and 14.3 MFLOP forward plus backward per example, 1.84 GFLOP
per DP step of B=128, 317,124 parameters, and 162 MB of float32
per-example gradients per step.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def testbed_fields(cfg: dict) -> dict:
    """The ``TestbedConfig`` keywords of this family."""
    from repro.data.synthetic_ser import SERDataConfig
    from repro.models.ser_cnn import SERConfig
    return {"data": SERDataConfig(**cfg["data"]),
            "model": SERConfig(**cfg["model"]), "workload": "ser_cnn"}


# ---------------------------------------------------------------------------
# corpus
# ---------------------------------------------------------------------------

def _smooth(rng, n, length, smooth=6):
    z = rng.standard_normal((n, length + smooth))
    k = np.ones(smooth) / smooth
    out = np.stack([np.convolve(z[i], k, mode="valid")[:length]
                    for i in range(n)])
    return out / (out.std(axis=1, keepdims=True) + 1e-8)


def make_corpus(d: dict):
    """(x (N, T, M) float32, y (N,) int32) from the corpus parameters."""
    rng = np.random.default_rng(d["seed"])
    T, M, R = d["time_frames"], d["n_mels"], d["rank"]
    u, v = _smooth(rng, R, T), _smooth(rng, R, M)
    cls = rng.standard_normal((d["n_classes"], R)) * d["class_gain"]
    ns, sr = d["n_speakers"], d["speaker_rank"]
    su = _smooth(rng, ns * sr, T).reshape(ns, sr, T)
    sv = _smooth(rng, ns * sr, M).reshape(ns, sr, M)
    sb = rng.standard_normal((ns, sr)) * d["speaker_gain"]
    n = d["n_total"]
    y_true = rng.integers(0, d["n_classes"], size=n)
    spk = rng.integers(0, ns, size=n)
    coeffs = cls[y_true] + d["coeff_jitter"] * rng.standard_normal((n, R))
    x = np.einsum("nr,rt,rm->ntm", coeffs, u, v)
    x += np.einsum("ns,nst,nsm->ntm", sb[spk], su[spk], sv[spk])
    x += d["noise"] * rng.standard_normal((n, T, M))
    x = (x - x.mean()) / (x.std() + 1e-8)
    y = y_true.copy()
    if d["label_noise"] > 0:
        flip = rng.random(n) < d["label_noise"]
        y[flip] = rng.integers(0, d["n_classes"], size=int(flip.sum()))
    return x.astype(np.float32), y.astype(np.int32)


class Corpus:
    """The corpus, on the device and (labels) on the host: built once per
    configuration and shared by every seed, the reference and control."""

    def __init__(self, cfg: dict):
        x, y = make_corpus(cfg["data"])
        self.labels = y
        self.x = jnp.asarray(x)
        self.y = jnp.asarray(y)


# ---------------------------------------------------------------------------
# the SER CNN
# ---------------------------------------------------------------------------

def init_params(key, m: dict):
    k1, k2, k3, k4 = jax.random.split(key, 4)

    def uni(k, shape, fan_in):
        s = 1.0 / jnp.sqrt(fan_in)
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    c1, c2, ks, nm = m["channels1"], m["channels2"], m["kernel"], m["n_mels"]
    flat = (m["time_frames"] // 4) * c2
    return {
        "conv1": {"w": uni(k1, (ks, nm, c1), nm * ks), "b": jnp.zeros((c1,))},
        "gn1": {"scale": jnp.ones((c1,)), "bias": jnp.zeros((c1,))},
        "conv2": {"w": uni(k2, (ks, c1, c2), c1 * ks), "b": jnp.zeros((c2,))},
        "gn2": {"scale": jnp.ones((c2,)), "bias": jnp.zeros((c2,))},
        "fc1": {"w": uni(k3, (flat, m["fc_dim"]), flat),
                "b": jnp.zeros((m["fc_dim"],))},
        "out": {"w": uni(k4, (m["fc_dim"], m["num_classes"]), m["fc_dim"]),
                "b": jnp.zeros((m["num_classes"],))},
    }


def logits(p, x, groups: int):
    """x: (T, n_mels) -> class logits; convs over time, SAME padding."""
    def conv(h, q):
        return jax.lax.conv_general_dilated(
            h[None], q["w"], (1,), "SAME",
            dimension_numbers=("NWC", "WIO", "NWC"))[0] + q["b"]

    def gnorm(h, q):
        t, c = h.shape
        g = h.reshape(t, groups, c // groups)
        mu = g.mean(axis=(0, 2), keepdims=True)
        var = g.var(axis=(0, 2), keepdims=True)
        g = (g - mu) * jax.lax.rsqrt(var + 1e-5)
        return g.reshape(t, c) * q["scale"] + q["bias"]

    def pool(h):
        t, c = h.shape
        return h.reshape(t // 2, 2, c).max(axis=1)

    h = pool(jax.nn.relu(gnorm(conv(x, p["conv1"]), p["gn1"])))
    h = pool(jax.nn.relu(gnorm(conv(h, p["conv2"]), p["gn2"])))
    h = jax.nn.relu(h.reshape(-1) @ p["fc1"]["w"] + p["fc1"]["b"])
    return h @ p["out"]["w"] + p["out"]["b"]


def loss(p, x, y, m: dict):
    """One example's cross-entropy: x (T, n_mels), y its label."""
    return -jax.nn.log_softmax(logits(p, x, m["gn_groups"]))[y]


# ---------------------------------------------------------------------------
# operation counts
# ---------------------------------------------------------------------------

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


def param_count(m: dict) -> int:
    t, k = m["time_frames"], m["kernel"]
    c0, c1, c2, f = m["n_mels"], m["channels1"], m["channels2"], m["fc_dim"]
    return (k * c0 * c1 + c1 + 2 * c1          # conv1 + GroupNorm 1
            + k * c1 * c2 + c2 + 2 * c2        # conv2 + GroupNorm 2
            + (t // 4) * c2 * f + f            # FC
            + f * m["num_classes"] + m["num_classes"])
