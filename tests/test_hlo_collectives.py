"""HLO-walker collective accounting: verify traffic conventions on
programs with KNOWN collective content (requires >1 device => spawn a
subprocess with forced host devices so the main test session keeps its
single CPU device)."""
import json
import subprocess
import sys

import pytest

_PROBE = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, json
sys.path.insert(0, "src"); sys.path.insert(0, ".")
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from benchmarks.hlo_analysis import analyze
from repro.launch.mesh import auto_mesh

mesh = auto_mesh((8,), ("x",))
sh = NamedSharding(mesh, P("x"))
repl = NamedSharding(mesh, P())

# psum over sharded contraction: y = sum over the sharded dim
def f(a, b):
    return a @ b     # (64, 128@x) @ (128@x, 32): contraction sharded -> AR

a_sh = NamedSharding(mesh, P(None, "x"))
b_sh = NamedSharding(mesh, P("x", None))
jitted = jax.jit(f, in_shardings=(a_sh, b_sh), out_shardings=repl)
txt = jitted.lower(
    jax.ShapeDtypeStruct((64, 128), jnp.float32),
    jax.ShapeDtypeStruct((128, 32), jnp.float32),
).compile().as_text()
res = analyze(txt)
out = {"ar_traffic": res["collective_traffic_bytes"].get("all-reduce", 0.0),
       "counts": res["collective_counts"],
       "flops": res["dot_flops"]}
print(json.dumps(out))
"""


@pytest.mark.parametrize("probe", [_PROBE])
def test_allreduce_convention(probe):
    r = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=300, cwd=".")
    assert r.returncode == 0, r.stderr[-800:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    # one all-reduce of the (64, 32) f32 output: ring traffic
    # 2*(g-1)/g * bytes = 2*(7/8)*8192 = 14336
    assert out["counts"].get("all-reduce", 0) >= 1
    expected = 2 * (7 / 8) * 64 * 32 * 4
    assert abs(out["ar_traffic"] - expected) / expected < 0.5, out
    # per-device dot flops: full output x sharded contraction
    # = 2 * 64*32 * (128/8) = 65536
    assert out["flops"] == pytest.approx(2 * 64 * 32 * 16, rel=0.01), out
