import pytest

from bench.arch.dense_gqa import DenseShape
from bench.flops import gemm_bytes, gemm_flops, least_time_s
from bench.model import ModelSpec
from bench.peaks import peak_for

V5E = peak_for("TPU v5 lite")


def test_known_gemm():
    assert gemm_flops(4096, 4096, 4096) == 2 * 4096 ** 3
    assert gemm_bytes(64, 1536, 576) == 2 * (64 * 576 + 576 * 1536 + 64 * 1536)
    # A and C held in VMEM: only the weight moves through HBM
    assert gemm_bytes(16, 17408, 5120, in_hbm=(False, True, False)) == \
        2 * 5120 * 17408
    # 4096^3 is compute-bound: 137.4 GFLOP at 197 TFLOP/s
    t = least_time_s(gemm_flops(4096, 4096, 4096),
                     gemm_bytes(4096, 4096, 4096), V5E)
    assert t == pytest.approx(2 * 4096 ** 3 / 197e12)
    # a skinny decode GEMM is bound by reading its weight
    t = least_time_s(gemm_flops(16, 17408, 5120),
                     gemm_bytes(16, 17408, 5120), V5E)
    assert t == pytest.approx(gemm_bytes(16, 17408, 5120) / 819e9)


def test_one_decoder_layer_of_qwen3():
    s = ModelSpec.load("qwen3-14b").shape
    # q, k, v, o, gate, up, down at d 5120, 40/8 heads of 128, d_ff 17408
    per_layer = (5120 * 5120 * 2 + 5120 * 1024 * 2 + 3 * 5120 * 17408)
    assert s.projection_params() == per_layer == 330_301_440
    gemms = [(1536, n, k) for _, n, k in s.projections()]
    assert len(gemms) == 7
    assert sum(gemm_flops(*g) for g in gemms) == 2 * 1536 * per_layer
    # every projection GEMM of a 1536-token prefill is compute-bound
    least = sum(least_time_s(gemm_flops(*g), gemm_bytes(*g), V5E)
                for g in gemms)
    assert least == pytest.approx(2 * 1536 * per_layer / 197e12)


def test_smollm_layer_and_request_flops():
    s = ModelSpec.load("smollm-135m").shape
    assert s.projection_params() == 576 * 576 * 2 + 576 * 192 * 2 \
        + 3 * 576 * 1536
    tiny = DenseShape(layers=2, d_model=4, n_heads=2, n_kv=1, head_dim=2,
                      d_ff=8, vocab=10, qk_norm=False, rope_theta=1e4,
                      norm_eps=1e-6)
    # 3 prompt tokens, 1 token decoded: 4 tokens through both layers'
    # projections, contexts 1+2+3+4 = 10 in each layer, head at 2 positions
    proj = 4 * 4 + 4 * 2 * 2 + 4 * 4 + 3 * 4 * 8
    assert tiny.projection_params() == proj
    want = 2 * 2 * proj * 4 + 4 * 2 * 2 * 2 * 10 + 2 * 4 * 10 * 2
    assert tiny.request_model_flops(3, 1) == want
