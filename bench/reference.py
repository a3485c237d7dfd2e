"""Plain reference of one FedAsync experiment, written from the semantics
and importing nothing of the program under test.

It rebuilds, from the configuration and the seed alone:

* the synthetic SER corpus (shared low-rank class basis, speaker fields,
  Gaussian noise, label flips), its class-balanced iid split over the
  clients and each client's 80/20 train/test split;
* each client's tier clock (lognormal compute time, exchange latency,
  dropout penalty) and its minibatch permutations;
* the SER 1D-CNN (two conv+GroupNorm+ReLU+maxpool blocks, FC, output)
  and its initial weights;
* per-example DP-SGD: per-example gradients, clipping of each to C over
  all leaves, the batch mean, Gaussian noise of stddev sigma*C/B drawn
  leaf by leaf, then Adam, with every client keeping its Adam state
  across its rounds;
* FedAsync: completions popped from the virtual-clock heap in cohorts
  (all events within the staleness window of the earliest, at most
  ``max_cohort``, cut to a power of two), merged with alpha/(1+tau);
* the moments accountant's epsilon for the subsampled Gaussian at the
  integer orders below.

``simulate`` returns the final global params and the run's books: merged
updates, staleness and epsilon per tier, the virtual times of the evals
and the cohort sizes.  ``dtype`` runs every tensor of the training in
that dtype: ``jnp.bfloat16`` is the lower-precision control.
"""
from __future__ import annotations

import heapq
import math

import jax
import jax.numpy as jnp
import numpy as np

TIERS = ("HW_T1", "HW_T2", "HW_T3", "HW_T4", "HW_T5")
# tier clock: (compute_time_s, lognormal jitter, exchange_latency_s,
#              dropout_per_round, dropout_penalty_s)
CLOCKS = {
    "HW_T1": (540.0, 0.22, 0.175, 0.05, 180.0),
    "HW_T2": (470.0, 0.20, 0.16, 0.033, 150.0),
    "HW_T3": (230.0, 0.12, 0.09, 0.0, 0.0),
    "HW_T4": (72.0, 0.06, 0.027, 0.0, 0.0),
    "HW_T5": (66.0, 0.05, 0.025, 0.0, 0.0),
}
ORDERS = tuple(range(1, 65)) + (80, 96, 128, 192, 256, 512)
DELTA = 1e-5


# ---------------------------------------------------------------------------
# corpus and split
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


def client_rows(y, num_clients: int, seed: int):
    """Per client, (train rows, test rows) as indices into the corpus:
    class-balanced iid shards, then an 80/20 split seeded per client."""
    rng = np.random.default_rng(seed)
    shards = [[] for _ in range(num_clients)]
    for c in np.unique(y):
        idx = rng.permutation(np.where(y == c)[0])
        for i, chunk in enumerate(np.array_split(idx, num_clients)):
            shards[i].append(chunk)
    out = []
    for cid, parts in enumerate(shards):
        rows = rng.permutation(np.concatenate(parts))
        perm = np.random.default_rng(seed + cid).permutation(len(rows))
        n_test = int(len(rows) * 0.2)
        out.append((rows[perm[n_test:]], rows[perm[:n_test]]))
    return out


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


# ---------------------------------------------------------------------------
# one client's local round of per-example DP-SGD with Adam
# ---------------------------------------------------------------------------

_ROUNDS = {}


def round_fn(n_steps: int, m: dict, clip: float, lr: float, dtype,
             precision: str, half_batch: bool = False):
    """Jitted local round: (params, adam, xs (S,B,T,M), ys (S,B), key,
    stddev) -> (params, adam).  Built once per step count and dtype.
    ``half_batch`` plants a fault: each step leaves out the second half
    of its batch and takes the mean over the rest."""
    key = (n_steps, tuple(sorted(m.items())), clip, lr, jnp.dtype(dtype).name,
           precision, half_batch)
    if key in _ROUNDS:
        return _ROUNDS[key]
    groups = m["gn_groups"]
    b1, b2, eps = 0.9, 0.999, 1e-8
    one = jnp.asarray(1, dtype)

    def loss(p, x, y):
        return -jax.nn.log_softmax(logits(p, x.astype(dtype), groups))[y]

    def step(p, adam, x, y, sub, stddev):
        if half_batch:
            x, y = x[: x.shape[0] // 2], y[: y.shape[0] // 2]
        g = jax.vmap(jax.grad(loss), in_axes=(None, 0, 0))(p, x, y)
        leaves, tdef = jax.tree_util.tree_flatten(g)
        bsz = x.shape[0]
        norm = jnp.sqrt(sum(jnp.sum(jnp.square(l).reshape(bsz, -1), axis=1)
                            for l in leaves))
        scale = one / jnp.maximum(one, norm / jnp.asarray(clip, dtype))
        keys = jax.random.split(sub, len(leaves))
        mean = [jnp.mean(l * scale.reshape((bsz,) + (1,) * (l.ndim - 1)),
                         axis=0)
                + (jax.random.normal(k, l.shape[1:], jnp.float32)
                   * stddev).astype(dtype)
                for l, k in zip(leaves, keys)]
        g = jax.tree_util.tree_unflatten(tdef, mean)
        t, mu, nu = adam
        t = t + 1
        tf = t.astype(jnp.float32)
        mu = jax.tree_util.tree_map(lambda a, b: b1 * a + (1 - b1) * b, mu, g)
        nu = jax.tree_util.tree_map(lambda a, b: b2 * a + (1 - b2) * b * b,
                                    nu, g)
        c1 = (1 - b1 ** tf).astype(dtype)
        c2 = (1 - b2 ** tf).astype(dtype)
        p = jax.tree_util.tree_map(
            lambda w, a, b: (w - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps))
                             ).astype(dtype), p, mu, nu)
        return p, (t, mu, nu)

    def run(p, adam, xs, ys, key, stddev):
        with jax.default_matmul_precision(precision):
            for s in range(n_steps):
                key, sub = jax.random.split(key)
                p, adam = step(p, adam, xs[s], ys[s], sub, stddev)
        return p, adam

    fn = jax.jit(run)
    _ROUNDS[key] = fn
    return fn


@jax.jit
def _gather(x, rows):
    return jnp.take(x, rows, axis=0)


def _merge(g, members, coeffs, g_coeff, dtype):
    out = jax.tree_util.tree_map(lambda a: a * jnp.asarray(g_coeff, dtype), g)
    for c, m in zip(coeffs, members):
        out = jax.tree_util.tree_map(
            lambda a, b, c=c: a + jnp.asarray(c, dtype) * b, out, m)
    return out


# ---------------------------------------------------------------------------
# accountant
# ---------------------------------------------------------------------------

def _log_moments(q: float, sigma: float) -> np.ndarray:
    out = []
    for lam in ORDERS:
        a = lam + 1
        terms = [math.lgamma(a + 1) - math.lgamma(k + 1)
                 - math.lgamma(a - k + 1) + (a - k) * math.log1p(-q)
                 + k * math.log(q) + k * (k - 1) / (2.0 * sigma * sigma)
                 for k in range(a + 1)]
        mx = max(terms)
        out.append(mx + math.log(sum(math.exp(t - mx) for t in terms)))
    return np.array(out)


def epsilon(q: float, sigma: float, steps: int) -> float:
    """Epsilon at DELTA after ``steps`` subsampled-Gaussian steps."""
    if steps == 0:
        return 0.0
    if q >= 1.0:
        mu = np.array([lam * (lam + 1) / (2 * sigma * sigma)
                       for lam in ORDERS]) * steps
    else:
        mu = _log_moments(q, sigma) * steps
    return float(np.min((mu - math.log(DELTA)) / np.array(ORDERS, float)))


# ---------------------------------------------------------------------------
# the experiment
# ---------------------------------------------------------------------------

class Corpus:
    """The corpus, on the device and (labels) on the host: built once per
    configuration and shared by every seed, the reference and control."""

    def __init__(self, cfg: dict):
        x, y = make_corpus(cfg["data"])
        self.labels = y
        self.x = jnp.asarray(x)
        self.y = jnp.asarray(y)


def _pop(heap, window, max_size):
    events = [heapq.heappop(heap)]
    while heap and len(events) < max_size and heap[0][0] <= events[0][0] + window:
        events.append(heapq.heappop(heap))
    events.sort()
    keep = 1 << (len(events).bit_length() - 1)
    for ev in events[keep:]:
        heapq.heappush(heap, ev)
    return events[:keep]


def simulate(cfg: dict, traffic: dict, corpus: Corpus, seed: int,
             sigma: float, *, dtype=jnp.float32, precision: str | None = None,
             fault: str | None = None):
    """One FedAsync experiment; returns ``(init_params, final_params,
    books)``.  ``fault`` plants one fault in place of the program's:
    ``"unchanged"`` (every local round returns its params unchanged),
    ``"half_batch"`` (each DP step uses half its batch) or ``"answer"``
    (the last merged update's epsilon is off by one part in 10**6)."""
    tb, m = cfg["testbed"], cfg["model"]
    precision = precision or cfg.get("matmul_precision", "highest")
    n_clients, bsz = tb["num_clients"], tb["batch_size"]
    clip, lr, epochs = tb["clip_norm"], tb["lr"], tb["local_epochs"]
    alpha, window = traffic["alpha"], traffic["staleness_window"]
    max_k, max_updates = traffic["max_cohort"], traffic["max_updates"]
    eval_every = traffic["eval_every"]
    stddev = jnp.float32(sigma * clip / bsz)
    rows_of = client_rows(corpus.labels, n_clients, seed)

    p0 = init_params(jax.random.PRNGKey(seed), m)
    glob = jax.tree_util.tree_map(lambda a: a.astype(dtype), p0)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, glob)
    clocks, batch_rng, n_train, steps, adam = [], [], [], [], []
    for cid in range(n_clients):
        clocks.append(np.random.default_rng(seed * 977 + cid))
        batch_rng.append(np.random.default_rng(seed * 131 + cid))
        n = len(rows_of[cid][0])
        n_train.append(n)
        per_epoch = (n - bsz) // bsz + 1 if n >= bsz else 0
        steps.append(epochs * per_epoch)
        adam.append((jnp.zeros((), jnp.int32), zeros, zeros))
    rounds = [0] * n_clients
    eps_memo = {}

    def duration(cid):
        comp, jit_, lat, p_drop, pen = CLOCKS[TIERS[cid % 5]]
        r = clocks[cid]
        t = comp * float(r.lognormal(mean=0.0, sigma=jit_)) + lat
        if p_drop > 0 and r.random() < p_drop:
            t += pen
        return t

    def dispatch(cid, params, key, version):
        rounds[cid] += 1
        q = min(1.0, bsz / n_train[cid])
        k = (q, steps[cid] * rounds[cid])
        if k not in eps_memo:
            eps_memo[k] = epsilon(q, sigma, k[1])
        return {"cid": cid, "params": params, "key": key, "version": version,
                "eps": eps_memo[k], "dur": duration(cid)}

    key = jax.random.PRNGKey(seed)
    heap, pending = [], {}
    for cid in range(n_clients):
        key, sub = jax.random.split(key)
        plan = dispatch(cid, glob, sub, 0)
        pending[cid] = plan
        heap.append((plan["dur"], cid))
    heapq.heapify(heap)

    books = {"update_counts": {t: 0 for t in TIERS[:min(5, n_clients)]},
             "staleness": {}, "eps": {}, "times": [], "cohort_sizes": []}
    version, total = 0, 0
    while heap:
        events = _pop(heap, window, max_k)
        plans = []
        for t, cid in events:
            p = pending.pop(cid)
            p["t"] = t
            plans.append(p)
        t_virtual = plans[-1]["t"]
        news = []
        for p in plans:
            cid = p["cid"]
            idx = np.concatenate([
                batch_rng[cid].permutation(n_train[cid])[: steps[cid] // epochs * bsz]
                for _ in range(epochs)])
            rows = jnp.asarray(rows_of[cid][0][idx].reshape(steps[cid], bsz))
            fn = round_fn(steps[cid], m, clip, lr, dtype, precision,
                          fault == "half_batch")
            new, adam[cid] = fn(p["params"], adam[cid], _gather(corpus.x, rows),
                                _gather(corpus.y, rows), p["key"], stddev)
            news.append(p["params"] if fault == "unchanged" else new)
        ws, taus = [], []
        for i, p in enumerate(plans):
            tau = version + i - p["version"]
            taus.append(tau)
            ws.append(alpha / (1.0 + float(tau)))
        coeffs = np.empty(len(ws))
        rest = 1.0
        for i in range(len(ws) - 1, -1, -1):
            coeffs[i] = ws[i] * rest
            rest *= 1.0 - ws[i]
        glob = _merge(glob, news, coeffs.astype(np.float32), rest, dtype)
        version += len(plans)
        books["cohort_sizes"].append(len(plans))
        for p, tau in zip(plans, taus):
            tier = TIERS[p["cid"] % 5]
            books["update_counts"][tier] += 1
            books["staleness"].setdefault(tier, []).append(tau)
            books["eps"].setdefault(tier, []).append(p["eps"])
        before, total = total, total + len(plans)
        if total // eval_every > before // eval_every:
            books["times"].append(t_virtual)
        if total >= max_updates:
            if fault == "answer":
                books["eps"][tier][-1] *= 1.0 + 1e-6
            break
        for p in plans:
            key, sub = jax.random.split(key)
            plan = dispatch(p["cid"], glob, sub, version)
            pending[p["cid"]] = plan
            heapq.heappush(heap, (p["t"] + plan["dur"], p["cid"]))
    return p0, glob, books
