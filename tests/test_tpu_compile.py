"""Ahead-of-time compiles for a described TPU v5e (no chip needed).

The TPU compiler ships with jax and compiles for a chip that is described
rather than attached.  Mosaic refuses shapes the Pallas interpreter
accepts — blocks off the (8, 128) tile, a rank-1 block that is not a
multiple of 128 — so these compiles guard the DP clip kernel at the
paper's width (D = 317,124, the SER CNN) for the cohort sizes the engine
runs (K) and the batch sizes of the paper and the tests (B), and guard
that on a four-chip mesh the engine runs the kernel partitioned per
member instead of handing it to GSPMD (which refuses Mosaic kernels).

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the test workers
must all collect the same tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

D_PAPER = 317_124
V5E_HBM_BYTES = 16 * 2**30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def aot(topo):
    """Compiled kernels (interpret off) with the persistent compilation
    cache off: a compile for a described chip is written to the cache but
    cannot be read back without one."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    from repro.kernels.common import set_interpret_override
    prev_cache = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    prev_interp = set_interpret_override(False)
    yield
    set_interpret_override(prev_interp)
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()


@pytest.mark.parametrize("k", [1, 2, 8])
@pytest.mark.parametrize("b", [32, 128])
def test_dp_clip_compiles_for_v5e(aot, one_chip, k, b):
    from repro.kernels.dp_clip.ops import dp_clip_mean_noise_cohort
    # a fresh jit: the module-level one may hold interpreted traces
    fn = jax.jit(dp_clip_mean_noise_cohort.__wrapped__,
                 static_argnames=("clip_norm",))

    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)

    compiled = fn.lower(sds((k, b, D_PAPER)), 1.0, sds(()),
                        sds((k, D_PAPER))).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < V5E_HBM_BYTES


def test_cohort_step_partitions_kernel_on_four_chip_mesh(aot, topo):
    """The Pallas cohort step on a 2x2 v5e mesh (tiny CNN, cohort of 4):
    it compiles, and every kernel launch sees one member's rows."""
    from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec
    from repro.core.testbed import TestbedConfig, build_testbed
    from repro.data.synthetic_ser import SERDataConfig
    from repro.engine.cohort_step import make_cohort_step
    from repro.engine.mesh_backend import CohortSharding
    from repro.models.ser_cnn import SERConfig

    dims = dict(time_frames=12, n_mels=12)
    tb = TestbedConfig(
        num_clients=4, batch_size=16, dp_path="pallas",
        data=SERDataConfig(n_total=320, **dims),
        model=SERConfig(channels1=8, channels2=16, fc_dim=32, **dims))
    clients, params, _, _ = build_testbed(tb)
    c0 = clients[0]
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    rule = CohortSharding(mesh)
    rep = NamedSharding(mesh, PartitionSpec())
    k, slots = 4, 8
    s_max = c0.n_train // c0.batch_size
    n_max = max(c.n_train for c in clients)

    def arena(shape, dtype):
        leaf = jax.ShapeDtypeStruct(shape, dtype)
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rule(leaf))

    def small(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=rep)

    a_params = jax.tree_util.tree_map(
        lambda l: arena((slots,) + l.shape, l.dtype), params)
    a_opt = jax.tree_util.tree_map(
        lambda l: arena(l.shape, l.dtype),
        jax.eval_shape(jax.vmap(c0.opt.init), a_params))
    a_data = {key: arena((slots, n_max) + v.shape[1:], v.dtype)
              for key, v in c0.data.items()}
    step, _ = make_cohort_step(
        c0.loss_fn, c0.dp_cfg, c0.opt, dp_path="pallas",
        client_axis="vmap", client_shardings=rule, arena=True)
    compiled = step.lower(
        a_params, a_opt, a_data, small((k,), jnp.int32),
        small((k,), jnp.int32), small((k, s_max, c0.batch_size), jnp.int32),
        small((k, 2), jnp.uint32), small((k,), jnp.int32),
        small((), jnp.float32), small((k,), jnp.float32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if "custom_call_target=\"tpu_custom_call\"" in ln]
    assert calls
    # the sqnorm pass returns one (rows, 1) column per launch: one
    # member's batch, not the cohort's k * batch rows
    assert any(f"f32[{c0.batch_size},1]" in ln for ln in calls)
    assert not any(f"f32[{k * c0.batch_size},1]" in ln for ln in calls)
