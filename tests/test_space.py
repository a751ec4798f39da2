"""Parameter-space legality (paper §4: X vs X-hat)."""

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core.space import (CONV_SPACE, GEMM_SPACE, SPACES, gemm_input,
                              conv_input, gemm_vmem_bytes, VMEM_LIMIT_BYTES)


def test_cardinality():
    assert GEMM_SPACE.cardinality() == np.prod(
        [len(v) for v in GEMM_SPACE.params.values()])
    assert GEMM_SPACE.cardinality() > 10_000      # a real search space


def test_enumerate_legal_nonempty_for_practical_inputs():
    for m, n, k in [(512, 512, 512), (2560, 16, 2560), (32, 32, 60000),
                    (4096, 4096, 32)]:
        legal = GEMM_SPACE.enumerate_legal(gemm_input(m, n, k))
        assert legal, (m, n, k)


def test_legal_subset_of_possible():
    inputs = gemm_input(256, 256, 4096)
    legal = GEMM_SPACE.enumerate_legal(inputs)
    for cfg in legal[:50]:
        assert GEMM_SPACE.contains(cfg)
        assert gemm_vmem_bytes(cfg, 16) <= VMEM_LIMIT_BYTES


@given(st.sampled_from([16, 32]),
       st.integers(5, 13), st.integers(4, 11), st.integers(5, 14))
@settings(max_examples=30, deadline=None)
def test_legality_invariants(bits, lm, ln, lk):
    """Property: every config accepted by is_legal respects VMEM, alignment
    and split bounds (the definition of X)."""
    inputs = gemm_input(2 ** lm, 2 ** ln, 2 ** lk, dtype_bits=bits)
    rng = np.random.default_rng(lm * 100 + ln * 10 + lk)
    names = GEMM_SPACE.param_names
    for _ in range(20):
        cfg = {n: int(rng.choice(GEMM_SPACE.params[n])) for n in names}
        if GEMM_SPACE.is_legal(cfg, inputs):
            assert gemm_vmem_bytes(cfg, bits) <= VMEM_LIMIT_BYTES
            assert cfg["bm"] % 8 == 0 and cfg["bn"] % 128 == 0
            k_steps = -(-inputs["K"] // cfg["bk"])
            assert cfg["k_split"] <= k_steps
            if bits == 32:
                assert cfg["acc32"] == 1


def test_conv_legal():
    inputs = conv_input(16, 24, 240, 32, 32, 3, 3)
    legal = CONV_SPACE.enumerate_legal(inputs)
    assert legal
    for cfg in legal[:20]:
        assert cfg["rs_unroll"] <= 9


def test_all_spaces_registered():
    assert set(SPACES) == {"gemm", "conv", "attention", "ssd"}
    for sp in SPACES.values():
        assert sp.cardinality() > 0 and sp.input_params
