"""Pipeline parallelism (gpipe over shard_map+ppermute).

Needs multiple devices, so the actual check runs in a subprocess with forced
host devices — the main test process must keep seeing ONE device.
"""

import subprocess
import sys

CHILD = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.parallel.pipeline import pipeline_apply, stage_split

mesh = jax.make_mesh((4,), ("stage",), axis_types=(AxisType.Auto,))
n_layers, d = 8, 16
ws = jax.random.normal(jax.random.PRNGKey(0), (n_layers, d, d)) * 0.2

def layer(w, x):
    return jnp.tanh(x @ w)

def stage_fn(params, x):       # params: (layers_per_stage, d, d)
    for i in range(params.shape[0]):
        x = layer(params[i], x)
    return x

x = jax.random.normal(jax.random.PRNGKey(1), (6, 4, d))   # 6 microbatches
stage_params = stage_split(ws, 4)
got = pipeline_apply(stage_fn, stage_params, x, mesh=mesh)

ref = x
for i in range(n_layers):
    ref = layer(ws[i], ref)
np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                           rtol=1e-5, atol=1e-5)
print("PP-OK")
"""


def test_gpipe_matches_sequential():
    r = subprocess.run([sys.executable, "-c", CHILD], capture_output=True,
                       text=True, timeout=300)
    assert "PP-OK" in r.stdout, r.stdout + r.stderr
