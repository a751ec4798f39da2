"""MoE layer: routing, capacity, EP shard_map path, decode path."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import AxisType

from repro.models.moe import (init_moe, moe, moe_decode, moe_ep, _route,
                              _capacity)


@pytest.fixture(scope="module")
def layer():
    p = init_moe(jax.random.PRNGKey(0), 32, 64, 4, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 32))
    return p, x


def test_moe_matches_dense_when_no_drops(layer):
    p, x = layer
    out, aux = moe(p, x, n_experts=4, top_k=2, capacity_factor=8.0)
    ref = moe_decode(p, x, n_experts=4, top_k=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    assert 0.5 < float(aux) < 4.0       # balanced-ish at init


def test_capacity_drops_reduce_output(layer):
    """Tiny capacity: some tokens dropped -> output differs from dense."""
    p, x = layer
    out_small, _ = moe(p, x, n_experts=4, top_k=2, capacity_factor=0.25)
    ref = moe_decode(p, x, n_experts=4, top_k=2)
    assert np.abs(np.asarray(out_small) - np.asarray(ref)).max() > 1e-3


def test_moe_ep_single_device_mesh(layer):
    """shard_map EP path on a 1-device mesh must equal the reference path."""
    p, x = layer
    mesh = jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    out_ep, aux_ep = moe_ep(p, x, n_experts=4, top_k=2,
                            capacity_factor=8.0, mesh=mesh)
    out_ref, aux_ref = moe(p, x, n_experts=4, top_k=2, capacity_factor=8.0)
    np.testing.assert_allclose(np.asarray(out_ep), np.asarray(out_ref),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(float(aux_ep), float(aux_ref), rtol=1e-5)


def test_route_renormalizes():
    logits = jnp.asarray([[0.0, 10.0, 0.0, 5.0]])
    w, idx = _route(logits, 2)
    assert np.allclose(np.asarray(w).sum(-1), 1.0)
    assert set(np.asarray(idx)[0]) == {1, 3}


def test_capacity_formula():
    assert _capacity(4096, 4, 16, 1.25) == 1280
    assert _capacity(1, 1, 128, 1.0) == 1


def test_moe_a2a_matches_on_multidevice():
    """All-to-all EP == reference MoE on a real 4-device mesh (subprocess:
    the main process must keep one device).  Aux loss is per-shard averaged
    (a deliberate, slightly different load-balance objective) — outputs must
    match exactly."""
    import subprocess
    import sys
    child = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys; sys.path.insert(0, "src")
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import AxisType
from repro.models.moe import init_moe, moe, moe_ep, moe_ep_a2a
mesh = jax.make_mesh((1, 4), ("data", "model"),
                     axis_types=(AxisType.Auto,) * 2)
p = init_moe(jax.random.PRNGKey(0), 32, 64, 8, jnp.float32)
x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
ref, _ = moe(p, x, n_experts=8, top_k=2, capacity_factor=8.0)
ep, _ = moe_ep(p, x, n_experts=8, top_k=2, capacity_factor=8.0, mesh=mesh)
np.testing.assert_allclose(np.asarray(ep), np.asarray(ref), rtol=1e-4, atol=1e-5)
a2a, _ = moe_ep_a2a(p, x, n_experts=8, top_k=2, capacity_factor=8.0, mesh=mesh)
np.testing.assert_allclose(np.asarray(a2a), np.asarray(ref), rtol=1e-4, atol=1e-5)
print("A2A-OK")
"""
    r = subprocess.run([sys.executable, "-c", child], capture_output=True,
                       text=True, timeout=380)
    assert "A2A-OK" in r.stdout, r.stdout + r.stderr
