"""Per-kernel correctness: Pallas (interpret=True) vs ref.py oracles,
swept over shapes / dtypes / tuning configurations."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.experimental import pallas as pl

from repro.kernels import dispatch, ops, ref

GEMM_CONFIGS = [
    {"bm": 8, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 1,
     "order": 0, "acc32": 1, "prefetch": 2},
    {"bm": 64, "bn": 128, "bk": 128, "k_unroll": 2, "k_split": 2,
     "order": 1, "acc32": 1, "prefetch": 2},
    {"bm": 128, "bn": 256, "bk": 256, "k_unroll": 1, "k_split": 4,
     "order": 0, "acc32": 1, "prefetch": 3},
    {"bm": 32, "bn": 128, "bk": 128, "k_unroll": 4, "k_split": 1,
     "order": 0, "acc32": 0, "prefetch": 1},
]

GEMM_SHAPES = [(96, 200, 512), (256, 256, 256), (17, 130, 1000),
               (512, 16, 384)]


@pytest.mark.parametrize("cfg", GEMM_CONFIGS)
@pytest.mark.parametrize("shape", GEMM_SHAPES)
def test_gemm_allclose(cfg, shape, rng):
    M, N, K = shape
    for dtype in (jnp.float32, jnp.bfloat16):
        if dtype == jnp.float32 and not cfg["acc32"]:
            continue
        a = jnp.asarray(rng.normal(size=(M, K)), dtype)
        b = jnp.asarray(rng.normal(size=(K, N)) / K ** 0.5, dtype)
        got = np.asarray(ops.matmul(a, b, cfg), np.float32)
        want = np.asarray(ref.matmul_ref(a, b), np.float32)
        tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
        scale = max(np.abs(want).max(), 1e-6)
        assert np.abs(got - want).max() / scale < tol, cfg


@given(st.integers(1, 3), st.integers(3, 5), st.integers(3, 5),
       st.sampled_from([1, 16, 33]), st.sampled_from([32, 128]),
       st.sampled_from([(1, 1), (3, 3), (1, 5)]))
@settings(max_examples=8, deadline=None)
def test_conv_allclose_property(n, lh, lw, c, k, rs):
    h, w = 2 ** lh, 2 ** lw
    r, s = rs
    rng = np.random.default_rng(n * 1000 + c)
    i = jnp.asarray(rng.normal(size=(n, h, w, c)), jnp.float32)
    f = jnp.asarray(rng.normal(size=(r, s, c, k)) / (r * s * c) ** 0.5,
                    jnp.float32)
    cfg = {"b_npq": 64, "b_k": 128, "b_c": 32, "rs_unroll": 1,
           "c_split": 2 if c > 32 else 1, "order": 0, "acc32": 1,
           "prefetch": 2}
    got = np.asarray(ops.conv2d(i, f, cfg))
    want = np.asarray(ref.conv2d_ref(i, f))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("b_q,b_kv", [(64, 64), (128, 256)])
@pytest.mark.parametrize("causal", [True, False])
def test_attention_allclose(b_q, b_kv, causal, rng):
    B, Hq, Hkv, Lq, Lkv, D = 2, 4, 2, 192, 192, 32
    q = jnp.asarray(rng.normal(size=(B, Hq, Lq, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, Hkv, Lkv, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, Hkv, Lkv, D)), jnp.float32)
    cfg = {"b_q": b_q, "b_kv": b_kv, "acc32": 1, "prefetch": 2}
    got = np.asarray(ops.flash_attention(q, k, v, cfg, causal=causal))
    want = np.asarray(ref.attention_ref(q, k, v, causal=causal))
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("chunk,b_heads", [(32, 1), (64, 2)])
def test_ssd_allclose(chunk, b_heads, rng):
    B, L, H, P, S = 2, 160, 4, 16, 32
    x = jnp.asarray(rng.normal(size=(B, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.2, (B, L, H)), jnp.float32)
    a = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(B, L, S)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(B, L, S)), jnp.float32)
    cfg = {"chunk": chunk, "b_heads": b_heads, "acc32": 1, "prefetch": 2}
    got = np.asarray(ops.ssd_scan(x, dt, a, bm, cm, cfg))
    want = np.asarray(ref.ssd_ref(x, dt, a, bm, cm))
    np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-3)


def test_check_config_gate():
    """The InterpretBackend correctness gate catches what it should."""
    dispatch.check_config(
        "gemm",
        {"bm": 64, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 2,
         "order": 0, "acc32": 1, "prefetch": 2},
        {"M": 128, "N": 128, "K": 512, "dtype_bits": 16})


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False)])
def test_interpret_default_follows_backend(monkeypatch, backend, interpret):
    """Every kernel entry point left at its default runs compiled on a TPU
    and interpreted on any other backend."""
    seen = []

    def fake_pallas_call(kernel, *, out_shape, interpret, **kwargs):
        seen.append(interpret)
        return lambda *args: jnp.zeros(out_shape.shape, out_shape.dtype)

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(pl, "pallas_call", fake_pallas_call)
    x = jnp.ones((16, 128), jnp.float32)
    ops.matmul(x, x.T)
    ops.conv2d(jnp.ones((1, 8, 8, 16)), jnp.ones((3, 3, 16, 128)))
    q = jnp.ones((1, 2, 16, 32))
    ops.flash_attention(q, q, q)
    ops.ssd_scan(jnp.ones((1, 32, 2, 16)), jnp.ones((1, 32, 2)),
                 -jnp.ones((2,)), jnp.ones((1, 32, 16)), jnp.ones((1, 32, 16)))
    assert seen == [interpret] * 4
