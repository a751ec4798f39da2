"""The float32 reference against the program's own prefill and
decode-through-cache, at smoke size on the CPU, on weights the benchmark
made.  Both run in float32, so they agree to rounding."""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from bench.correctness import reference_for
from bench.model import ModelSpec, seed_key

from .conftest import TINY_CONFIG


def spec_for(program, qk_norm, tied=True):
    raw = {**TINY_CONFIG, "program": program,
           "architecture": {"kind": "dense_gqa", "qk_norm": qk_norm},
           "config": {**TINY_CONFIG["config"], "torch_dtype": "float32",
                      "num_hidden_layers": 3,
                      "tie_word_embeddings": tied}}
    return ModelSpec.from_dict(raw)


@pytest.mark.parametrize("program,qk_norm", [("smollm-135m", False),
                                             ("qwen3-14b", True)])
def test_reference_matches_prefill_and_decode_through_the_cache(
        program, qk_norm):
    from repro.models import decode_step, init_cache, prefill

    spec = spec_for(program, qk_norm)
    cfg = spec.program_config()
    w = spec.make_weights(seed_key(7))
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, spec.shape.vocab, 21, dtype=np.int32)
    new = rng.integers(0, spec.shape.vocab, 5, dtype=np.int32)

    cache = init_cache(cfg, 1, spec.max_len)
    logits, cache = prefill(w, cfg, {"tokens": jnp.asarray(prompt[None])},
                            cache)
    got = [np.asarray(logits)[0, : spec.shape.vocab]]
    for i, t in enumerate(new[:-1]):
        logits, cache = decode_step(w, cfg, jnp.asarray([[t]]), cache,
                                    jnp.asarray(len(prompt) + i, jnp.int32))
        got.append(np.asarray(logits)[0, : spec.shape.vocab])
    got = np.stack(got)

    ref = reference_for(spec, w, length=64, n_positions=len(new))
    toks = np.concatenate([prompt, new[:-1]])
    want = np.asarray(ref.logits(toks, np.arange(len(prompt) - 1,
                                                 len(toks))))
    scale = np.abs(want).max()
    np.testing.assert_allclose(got, want, atol=2e-5 * scale, rtol=0)
    assert (got.argmax(-1) == want.argmax(-1)).all()


def test_padding_past_the_sequence_changes_no_logit():
    spec = spec_for("qwen3-14b", True)
    w = spec.make_weights(seed_key(3))
    toks = np.arange(1, 17, dtype=np.int32)
    a = reference_for(spec, w, length=32, n_positions=4)
    b = reference_for(spec, w, length=64, n_positions=4)
    pos = np.array([3, 9, 15])
    np.testing.assert_allclose(np.asarray(a.logits(toks, pos)),
                               np.asarray(b.logits(toks, pos)), rtol=1e-5,
                               atol=1e-6)


def test_untied_head_is_refused():
    """The program computes logits from its embedding table; a config with
    an untied head cannot be served, and is refused rather than served tied."""
    spec = spec_for("qwen3-14b", True, tied=False)
    with pytest.raises(ValueError, match="tied"):
        spec.program_config()


def test_e4m3_rounding():
    from bench.reference.dense_gqa import round_e4m3

    x = jnp.asarray([0.0, 1.0, 1.0625, 1.1875, 300.0, 500.0, -2.0 ** -9,
                     2.0 ** -10], jnp.float32)
    # 1.0625 lies halfway between 1 and 1.125 and rounds to even; e4m3
    # holds 288 and 320 near 300, saturates at 448, steps by 2^-9 at the
    # bottom
    np.testing.assert_array_equal(
        np.asarray(round_e4m3(x)),
        [0.0, 1.0, 1.0, 1.25, 288.0, 448.0, -2.0 ** -9, 0.0])


def test_weights_follow_the_served_dtype_and_seed():
    spec = dataclasses.replace(spec_for("qwen3-14b", True),
                               dtype=jnp.bfloat16)
    a = spec.make_weights(seed_key(2 ** 31 + 3))
    b = spec.make_weights(seed_key(2 ** 31 + 3))
    c = spec.make_weights(seed_key(4))
    assert a["embed"].dtype == jnp.bfloat16
    assert a["embed"].shape == (512, 64)
    assert a["layers"]["pos0"]["attn"]["wq"].shape == (3, 64, 64)
    np.testing.assert_array_equal(np.asarray(a["embed"], np.float32),
                                  np.asarray(b["embed"], np.float32))
    assert not np.array_equal(np.asarray(a["embed"], np.float32),
                              np.asarray(c["embed"], np.float32))
