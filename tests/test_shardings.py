"""Sharding-rule unit tests: leaf_spec decisions on realistic shapes.

Runs on the single CPU device (NamedSharding construction only touches
metadata, never allocates on the 256-chip mesh)."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import get_config


@pytest.fixture(scope="module")
def mesh():
    # AbstractMesh: shape metadata without devices
    return jax.sharding.AbstractMesh((16, 16), ("data", "model"))


def _spec(shape, cfg, mesh, role="master"):
    from repro.launch.shardings import leaf_spec
    return leaf_spec(shape, cfg, mesh, role)


def test_dense_master_rules(mesh):
    cfg = get_config("llama3.2-3b")
    D, H, Hkv, Dh, F, V = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                           cfg.head_dim, cfg.d_ff, cfg.vocab)
    L = cfg.n_layers
    # embedding: vocab over model, d_model ZeRO over data
    assert _spec((V, D), cfg, mesh) == P("model", "data")
    # mlp: ff over model, d_model over data
    assert _spec((L, D, F), cfg, mesh) == P(None, "data", "model")
    assert _spec((L, F, D), cfg, mesh) == P(None, "model", "data")
    # attention: 24 heads % 16 != 0 -> head_dim sharded (even rule)
    assert _spec((L, D, H, Dh), cfg, mesh) == P(None, "data", None, "model")
    # norm scales: d_model over data only
    assert _spec((L, D), cfg, mesh) == P(None, "data")


def test_moe_expert_sharding(mesh):
    cfg = get_config("olmoe-1b-7b")        # 64 experts, d_expert 1024
    L, E, D, F = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.d_expert
    spec = _spec((L, E, D, F), cfg, mesh)
    # one of experts / d_expert lands on model; d_model gets ZeRO data
    assert "model" in tuple(spec)
    assert spec[2] in ("data", None) or spec[1] in ("data",)


def test_client_role_leading_dim(mesh):
    cfg = get_config("smollm-360m")
    spec = _spec((16, cfg.n_layers, cfg.d_model, cfg.d_ff), cfg, mesh,
                 role="client")
    assert spec[0] == "data"
    assert "model" in tuple(spec)
    # d_model NOT ZeRO-sharded in client role (per-client copies)
    assert spec[2] is None


def test_client_all_axes_role(mesh):
    cfg = get_config("smollm-360m")
    spec = _spec((256, cfg.d_model, cfg.d_ff), cfg, mesh,
                 role="client_all_axes")
    assert spec[0] == ("data", "model")
    assert all(s is None for s in tuple(spec)[1:])


def test_serve_role_no_zero(mesh):
    cfg = get_config("gemma2-2b")
    spec = _spec((cfg.n_layers, cfg.d_model, cfg.d_ff), cfg, mesh,
                 role="serve")
    assert spec == P(None, None, "model")   # no data-axis ZeRO for serving


def test_all_archs_have_model_dim_on_big_leaves(mesh):
    """Every arch's ff-like matrices must shard over model (memory!)."""
    from repro.configs import ARCH_IDS
    from repro.launch.shardings import leaf_spec
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        if cfg.d_ff:
            spec = leaf_spec((cfg.n_layers, cfg.d_model, cfg.d_ff), cfg,
                             mesh, "master")
            assert "model" in tuple(spec), arch
