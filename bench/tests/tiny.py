"""Tiny cells for the CPU tests, written as their own checkout root
(``BENCHMARK.json`` plus ``bench/configs``, ``bench/families``,
``bench/traffic`` and ``bench/limits``): ``tiny``, the SER CNN at small
widths over six clients, and ``tiny-linear``, the same clients and
traffic with a second model family, ``ser_linear``: multinomial logistic
regression over the same corpus (the program's ``ser_linear``
workload), whose module the root holds beside a copy of ``ser_cnn``'s."""
from __future__ import annotations

import json
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]

MODEL = {"time_frames": 16, "n_mels": 16, "channels1": 32, "channels2": 64,
         "kernel": 5, "gn_groups": 8, "fc_dim": 64, "num_classes": 4}
DATA = {"n_total": 360, "n_classes": 4, "n_speakers": 9, "time_frames": 16,
        "n_mels": 16, "rank": 6, "speaker_rank": 2, "class_gain": 0.8,
        "speaker_gain": 1.0, "noise": 1.6, "coeff_jitter": 0.55,
        "label_noise": 0.12, "seed": 7}
TESTBED = {"num_clients": 6, "batch_size": 16, "local_epochs": 1, "lr": 0.001,
           "clip_norm": 1.0, "use_dp": True, "partition": "iid"}
TRAFFIC = {"strategy": "fedasync", "alpha": 0.4, "staleness_window": 45.0,
           "max_cohort": 2, "client_axis": "unroll", "pipeline_depth": 1,
           "dp_path": "jnp", "store": None, "mesh": False,
           "max_updates": 24, "eval_every": 8, "sigmas": [0.5, 1.0],
           "warmup_updates": 4}
PARAM_LIMITS = {"tree_dist": 0.01, "change_gap": 0.01}
LIMITS = {"limits": {"eps_gap": 1e-9, "books_diff": 0},
          "by_sigma": {repr(float(s)): PARAM_LIMITS
                       for s in TRAFFIC["sigmas"]}}
# cell -> model family
CELLS = {"tiny": "ser_cnn", "tiny-linear": "ser_linear"}

SER_LINEAR = '''"""The ``ser_linear`` model family: multinomial logistic regression over
the SER corpus, the program's ``ser_linear`` workload."""
import jax
import jax.numpy as jnp

from bench.families.ser_cnn import Corpus  # noqa: F401  the same corpus


def testbed_fields(cfg):
    from repro.data.synthetic_ser import SERDataConfig
    from repro.models.ser_cnn import SERConfig
    return {"data": SERDataConfig(**cfg["data"]),
            "model": SERConfig(**cfg["model"]), "workload": "ser_linear"}


def init_params(key, m):
    d = m["time_frames"] * m["n_mels"]
    s = 1.0 / jnp.sqrt(d)
    return {"w": jax.random.uniform(key, (d, m["num_classes"]), jnp.float32,
                                    -s, s),
            "b": jnp.zeros((m["num_classes"],), jnp.float32)}


def loss(p, x, y, m):
    return -jax.nn.log_softmax(x.reshape(-1) @ p["w"] + p["b"])[y]


def train_flops_per_example(m):
    return 3 * 2 * m["time_frames"] * m["n_mels"] * m["num_classes"]


def param_count(m):
    return (m["time_frames"] * m["n_mels"] + 1) * m["num_classes"]
'''


def write_root(root: Path, traffic: dict | None = None) -> str:
    """Write the tiny cells under ``root``; returns the SER CNN's name."""
    name = "tiny"
    for sub in ("configs", "families", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer = [{**m, "workloads": list(CELLS)} for m in real["per_layer"]]
    bench = {
        "end_to_end": real["end_to_end"],
        "per_layer": per_layer,
        "workloads": [{"name": cell, "config": cell, "traffic": name,
                       "chips": 1} for cell in CELLS],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    families = root / "bench" / "families"
    (families / "ser_cnn.py").write_text(
        (REPO / "bench" / "families" / "ser_cnn.py").read_text())
    (families / "ser_linear.py").write_text(SER_LINEAR)
    for cell, family in CELLS.items():
        (root / "bench" / "configs" / f"{cell}.json").write_text(json.dumps(
            {"family": family, "model": MODEL, "data": DATA,
             "testbed": TESTBED, "precision": "float32",
             "matmul_precision": "default"}))
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(LIMITS))
    (root / "bench" / "traffic" / f"{name}.json").write_text(
        json.dumps({**TRAFFIC, **(traffic or {})}))
    return name
