"""Ahead-of-time compiles of the Pallas kernels for a described TPU v5e.

Nothing runs: each case lowers a kernel at a real width for one chip of a
``v5e:2x2`` topology and compiles it with the TPU compiler, which refuses
what interpret mode accepts — a block that does not tile, an unsupported
primitive, more VMEM than the limit.  The topology is described inside a
fixture, never at import, so that under pytest-xdist only the worker that
runs this file loads the TPU library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import space
from repro.kernels import ops


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", os.environ.get("TPU_LOG_DIR", "disabled"))
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the
        # persistent cache without one: keep these out of it
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", was)


def _largest(sp, inputs, count):
    """The legal config of ``sp`` with the largest VMEM count at inputs."""
    return max(sp.enumerate_legal(inputs), key=lambda c: count(c, inputs))


def _gemm(M, N, K, cfg=None):
    return ((lambda a, b: ops.matmul(a, b, cfg, interpret=False)),
            [(M, K), (K, N)])


def _gmm(M, N, K, experts=64, layers=2):
    """The grouped GEMM as the served MoE path calls it: two layers'
    experts stacked, the second's groups holding the M rows evenly, with
    the shape rule's tiles."""
    G = experts * layers
    sizes = jnp.zeros((G,), jnp.int32).at[G - experts:].set(M // experts)
    tiles = space.gmm_default_tiles(M, N, K)
    return ((lambda a, b: ops.grouped_matmul(a, b, sizes, tiles,
                                             interpret=False)),
            [(M, K), (G, K, N)])


def _attention(Lq, Lkv, q_offset, cfg=None):
    B, Hq, Hkv, D = 1, 9, 3, 64                  # smollm-135m's heads
    return ((lambda q, k, v: ops.flash_attention(
                q, k, v, cfg, causal=True, q_offset=q_offset,
                interpret=False)),
            [(B, Hq, Lq, D), (B, Hkv, Lkv, D), (B, Hkv, Lkv, D)])


def _conv(cfg=None):
    return ((lambda i, f: ops.conv2d(i, f, cfg, interpret=False)),
            [(8, 56, 56, 128), (3, 3, 128, 128)])


def _ssd(cfg=None):
    B, L, H, P, S = 1, 1024, 8, 64, 64
    return ((lambda x, dt, a, b, c: ops.ssd_scan(x, dt, a, b, c, cfg,
                                                 interpret=False)),
            [(B, L, H, P), (B, L, H), (H,), (B, L, S), (B, L, S)])


ATTN_PREFILL = dict(B=1, Hq=9, Hkv=3, Lq=256, Lkv=256, D=64, dtype_bits=16,
                    causal=1)
CONV_LAYER = space.conv_input(8, 56, 56, 128, 128, 3, 3)
SSD_LAYER = dict(B=1, L=1024, H=8, P=64, S=64, dtype_bits=16)

CASES = {
    # smollm-135m's served GEMMs: decode (4 slots) and a 32-token prefill
    "gemm_decode": lambda: _gemm(4, 1536, 576),
    "gemm_prefill": lambda: _gemm(32, 576, 1536),
    # the shape rule's blocks at qwen3-14b's widths: the longest prefill
    # through w_gate and w_down, and 16 decode slots through w_gate
    "gemm_qwen3_prefill_gate": lambda: _gemm(3840, 17408, 5120),
    "gemm_qwen3_prefill_down": lambda: _gemm(3840, 5120, 17408),
    "gemm_qwen3_decode_gate": lambda: _gemm(16, 17408, 5120),
    # the config the compiler once refused for VMEM while the space
    # called it legal
    "gemm_4096_largest_legal": lambda: _gemm(4096, 4096, 4096, _largest(
        space.GEMM_SPACE, space.gemm_input(4096, 4096, 4096),
        lambda c, i: space.gemm_vmem_bytes(c, i["dtype_bits"]))),
    # mellum2-12b-a2.5b's expert GEMMs: 4096 prompt tokens x top-8 through
    # w_gate and w_down, and 16 decode slots x top-8 through w_gate
    "gmm_mellum_prefill_gate": lambda: _gmm(32768, 896, 2304),
    "gmm_mellum_prefill_down": lambda: _gmm(32768, 2304, 896),
    "gmm_mellum_decode_gate": lambda: _gmm(128, 896, 2304),
    "attention_prefill": lambda: _attention(256, 256, 0),
    "attention_decode": lambda: _attention(1, 256, 100),
    "attention_prefill_largest_legal": lambda: _attention(256, 256, 0, _largest(
        space.ATTENTION_SPACE, ATTN_PREFILL, space.attention_vmem_bytes)),
    "conv": lambda: _conv(),
    "conv_largest_legal": lambda: _conv(_largest(
        space.CONV_SPACE, CONV_LAYER, space.conv_vmem_bytes)),
    "ssd": lambda: _ssd(),
    "ssd_largest_legal": lambda: _ssd(_largest(
        space.SSD_SPACE, SSD_LAYER, space.ssd_vmem_bytes)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, shapes = CASES[case]()
    args = [jax.ShapeDtypeStruct(s, jnp.float32 if len(s) == 1
                                 else jnp.bfloat16, sharding=one_chip)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
