"""A tiny cell for the CPU tests: the SER CNN at small widths over six
clients, written as its own checkout root (``BENCHMARK.json`` plus
``bench/configs``, ``bench/traffic`` and ``bench/limits``)."""
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


def write_root(root: Path, traffic: dict | None = None) -> str:
    """Write the tiny cell under ``root``; returns its name."""
    name = "tiny"
    for sub in ("configs", "traffic", "limits"):
        (root / "bench" / sub).mkdir(parents=True, exist_ok=True)
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    per_layer = [{**m, "workloads": [name]} for m in real["per_layer"]]
    bench = {
        "end_to_end": real["end_to_end"],
        "per_layer": per_layer,
        "workloads": [{"name": name, "config": name, "traffic": name,
                       "chips": 1}],
    }
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    (root / "bench" / "configs" / f"{name}.json").write_text(json.dumps(
        {"model": MODEL, "data": DATA, "testbed": TESTBED,
         "precision": "float32", "matmul_precision": "default"}))
    (root / "bench" / "traffic" / f"{name}.json").write_text(
        json.dumps({**TRAFFIC, **(traffic or {})}))
    (root / "bench" / "limits" / f"{name}.json").write_text(
        json.dumps(LIMITS))
    return name
