"""SELL projections on the Pallas kernels under a (data=2, model=2) mesh.

XLA cannot partition a Mosaic kernel, so on a mesh of several devices
``models/linear.py`` runs the kernels under ``shard_map``, one batch shard
per device, with the O(N) parameters replicated.  The gradients of the
parameters and of the input must come out as on one device: the
parameters' cotangents summed over the batch shards once, not once per
model-axis replica.  Four virtual CPU devices need a process of their own.
"""

import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]

CHILD = r"""
import sys
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import registry
from repro.launch.mesh import make_host_mesh
from repro.models import linear

n_in, n_out, dtype = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
cfg = registry.with_sell(registry.get_smoke_config("qwen3_1_7b"), "acdc",
                         method="pallas")
role = "mlp_in"
assert linear.uses_sell(cfg, role)
p = linear.linear_init(jax.random.PRNGKey(0), n_in, n_out, cfg, role)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, n_in)).astype(dtype)

def loss(p, x):
    y = linear.linear_apply(p, x, n_in, n_out, cfg, role)
    return jnp.sum(jnp.tanh(y.astype(jnp.float32)) ** 2)

grad = jax.value_and_grad(loss, argnums=(0, 1))
l1, g1 = jax.jit(grad)(p, x)
mesh = make_host_mesh(2)
with jax.set_mesh(mesh):
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    l4, g4 = jax.jit(grad)(p, xs)
worst = 0.0
for path, a, b in zip(jax.tree_util.tree_leaves_with_path(g1),
                      jax.tree.leaves(g1), jax.tree.leaves(g4)):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    rel = np.linalg.norm(a - b) / np.linalg.norm(a)
    print(jax.tree_util.keystr(path[0]), a.shape, rel)
    worst = max(worst, rel)
print("LOSS", float(l1), float(l4))
print("WORST", worst)
"""


@pytest.mark.parametrize("n_in,n_out,dtype", [
    (256, 384, "float32"),      # one fused cascade kernel, reverse sweep
    (256, 384, "bfloat16"),
    (1152, 1152, "float32"),    # above MAX_FUSED_N: chained scaled matmuls
])
def test_sell_grads_on_mesh_match_one_device(n_in, n_out, dtype):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(ROOT / "src"))
    proc = subprocess.run(
        [sys.executable, "-c", CHILD, str(n_in), str(n_out), dtype],
        env=env, cwd=ROOT, capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    worst = float(proc.stdout.split("WORST")[-1])
    # one device and the mesh run the same kernels on the same rows; only
    # the order of the parameter cotangents' sum over rows differs
    assert worst < 1e-5, proc.stdout
