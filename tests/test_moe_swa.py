"""Serving a sparse-expert model whose layers mix sliding-window and full
attention (Mellum 2's layout), at a tiny size on the CPU: what
``Engine.generate`` samples from, in prefill and in decode through the
cache, against the benchmark's plain float32 reference
(``bench/reference/moe_swa.py``) on seeded weights."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.correctness import reference_for
from bench.model import ModelSpec, seed_key

SLIDING, FULL = "sliding_attention", "full_attention"
# two periods of (sliding x3, full), d 64, 8 experts of width 32 top-2,
# window 8, vocab 256, an untied head, all in float32
TINY = {
    "name": "tiny-moe-swa", "source": "test", "program": "mellum2-12b-a2.5b",
    "reference": "moe_swa", "architecture": {"kind": "moe_swa"},
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_hidden_layers": 8,
    "layer_types": [SLIDING] * 3 + [FULL] + [SLIDING] * 3 + [FULL],
    "mlp_layer_types": ["sparse"] * 8, "sliding_window": 8,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.138629436111989},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "vocab_size": 256,
    "config": {"torch_dtype": "float32"}, "serve": {"slots": 2, "max_len": 48}}
SPEC = ModelSpec.from_dict(TINY)


def serve(weights, prompts, max_new):
    """What the engine served, and the logits it sampled each token from,
    per request: prefill's, then each decode tick's for that slot."""
    from repro.serve.engine import Engine, ServeConfig

    engine = Engine(SPEC.program_config(), weights,
                    ServeConfig(slots=SPEC.slots, max_len=SPEC.max_len))
    seen = {}
    sample = engine._sample

    def recording(logits):
        fresh = [r for r in engine.slot_req if r is not None and not r.out]
        if fresh:                       # prefill: the request just placed
            seen.setdefault(id(fresh[0]), []).append(logits[0])
        else:                           # a tick: every occupied slot
            for s, r in enumerate(engine.slot_req):
                if r is not None:
                    seen.setdefault(id(r), []).append(logits[s])
        return sample(logits)

    engine._sample = recording
    outs = engine.generate(prompts, max_new=max_new)
    return outs, [np.stack(seen[id(r)]) for r in engine.last_requests]


def check_against_reference(weights, prompts, max_new):
    outs, got = serve(weights, prompts, max_new)
    ref = reference_for(SPEC, weights, length=SPEC.max_len,
                        n_positions=max_new)
    for prompt, out, g in zip(prompts, outs, got):
        assert len(out) == max_new
        toks = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        want = np.asarray(ref.logits(toks, np.arange(len(prompt) - 1,
                                                     len(toks))))
        # both float32: they differ by the order of summation only
        np.testing.assert_allclose(g, want, atol=2e-5 * np.abs(want).max(),
                                   rtol=0)
        assert (g.argmax(-1) == np.asarray(out)).all()


def test_prompts_past_the_window_and_decode_that_wraps_the_ring():
    """Prompts of 13 and 21 tokens against an 8-position window, three
    requests through two slots, 14 tokens each: decode writes the ring
    past its end several times, and a slot is reused."""
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (13, 21, 9)]
    check_against_reference(SPEC.make_weights(seed_key(5)), prompts, 14)


def test_a_router_that_sends_every_token_to_the_same_experts():
    """A zero router gives every expert the same probability, so every
    token goes to experts 0 and 1 (top-k keeps the lowest ids among ties),
    weighted 1/2 each: two groups of every token.  The capacity path
    (``moe`` at capacity factor 1.25) would drop most of them."""
    from repro.models.moe import _capacity, moe, moe_dropless

    w = SPEC.make_weights(seed_key(6))
    for pos in w["layers"].values():
        pos["moe"]["router"] = jnp.zeros_like(pos["moe"]["router"])
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 256, n, dtype=np.int32) for n in (17, 11)]
    check_against_reference(w, prompts, 6)

    p = jax.tree_util.tree_map(lambda t: t[0], w["layers"]["pos0"]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 17, 64))
    out, rows = moe_dropless(p, x, n_experts=8, top_k=2)
    assert rows.tolist() == [17, 17, 0, 0, 0, 0, 0, 0]
    assert _capacity(17, 2, 8, 1.25) < 17
    capped, _ = moe(p, x, n_experts=8, top_k=2, capacity_factor=1.25)
    assert not np.allclose(np.asarray(capped), np.asarray(out), atol=1e-3)


def test_yarn_matches_its_closed_form():
    """The published full layers' RoPE (theta 5e5, factor 16 from 8192
    positions, beta 32 and 1, attention factor 1.2773): the correction
    dimensions are floor(18.08) = 18 and ceil(34.98) = 35 of the 64
    frequencies, below 18 the plain 1/theta^(2i/128), above 35 that over
    16, linear in between; cos and sin scale by 0.1 ln 16 + 1."""
    from repro.configs import get_config
    from repro.models.layers import apply_rope

    cfg = get_config("mellum2-12b-a2.5b")
    yarn, theta = cfg.rope_yarn, cfg.rope_theta
    plain = 1.0 / theta ** (np.arange(64) * 2 / 128)
    ramp = np.clip((np.arange(64) - 18) / (35 - 18), 0, 1)
    want = plain * (1 - ramp) + plain / 16 * ramp
    # float32 frequencies against float64 arithmetic
    np.testing.assert_allclose(yarn.inv_freq(128, theta), want, rtol=1e-6)
    assert yarn.attention_factor == pytest.approx(0.1 * math.log(16) + 1)

    x = jnp.zeros((1, 1, 1, 128)).at[..., 0].set(1.0)     # x1 = e_0
    pos = jnp.asarray([[5000]])
    got = np.asarray(apply_rope(x, pos, theta, yarn))[0, 0, 0]
    ang = 5000 * want[0]
    # float32 angles of 5000 rad: about 5e-4 of rounding in cos and sin
    np.testing.assert_allclose(
        [got[0], got[64]],
        [yarn.attention_factor * math.cos(ang),
         yarn.attention_factor * math.sin(ang)], atol=2e-3)


# The smoke qwen3-14b config's logits (tied head) on seeded weights, from
# the program before the untied head existed: prefill of 9 tokens, then
# three decode steps; the first four logits of each and the sum of |all|.
TIED_FIRST = [
    [-0.073806, -0.202111, 0.013859, -0.064177],
    [-0.003119, -0.11625, -0.042023, 0.258808],
    [0.254018, -0.224664, -0.152012, 0.279924],
    [-0.150678, -0.122074, 0.596784, -0.11775]]
TIED_ABS_SUM = 265.918701171875


def test_tied_configs_logits_are_unchanged():
    import dataclasses

    from repro.configs import smoke_config
    from repro.models import decode_step, init_cache, init_params, prefill

    cfg = dataclasses.replace(smoke_config("qwen3-14b"), remat=False)
    params = init_params(cfg, jax.random.PRNGKey(3))
    assert "lm_head" not in params
    toks = jnp.asarray(np.random.default_rng(3).integers(
        0, cfg.vocab, (1, 12)), jnp.int32)
    cache = init_cache(cfg, 1, 16)
    lg, cache = prefill(params, cfg, {"tokens": toks[:, :9]}, cache)
    out = [lg[0]]
    for t in range(9, 12):
        lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache,
                                jnp.asarray(t))
        out.append(lg[0])
    out = np.asarray(jnp.stack(out))[:, :cfg.vocab]
    # float32 on the CPU: the stored values are rounded to 1e-6
    np.testing.assert_allclose(out[:, :4], TIED_FIRST, atol=1e-6, rtol=0)
    assert float(np.abs(out).sum()) == pytest.approx(TIED_ABS_SUM, rel=1e-6)


def test_untied_head_is_its_own_table():
    from repro.models import init_params
    from repro.models.model import _logits

    cfg = SPEC.program_config()
    params = init_params(cfg, jax.random.PRNGKey(0))
    assert params["lm_head"].shape == params["embed"].shape
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 64))
    want = jnp.einsum("bsd,vd->bsv", x, params["lm_head"])
    np.testing.assert_allclose(np.asarray(_logits(cfg, params, x)),
                               np.asarray(want), rtol=1e-6, atol=1e-6)


def test_grouped_gemm_kernel_matches_ragged_dot():
    """The Pallas grouped GEMM (interpreted here) against XLA's ragged dot
    on groups as the served path passes them: two layers' experts
    stacked, the first layer's groups empty, some of the second's too."""
    from repro.kernels import dispatch

    rng = np.random.default_rng(2)
    sizes = np.zeros(16, np.int32)
    sizes[8:] = [9, 0, 4, 0, 17, 1, 0, 9]
    lhs = jnp.asarray(rng.normal(size=(40, 128)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(16, 128, 256)), jnp.bfloat16)
    got = dispatch.grouped_matmul(lhs, rhs, jnp.asarray(sizes),
                                  prefer_kernel=True)
    want = jax.lax.ragged_dot(lhs, rhs, jnp.asarray(sizes),
                              preferred_element_type=jnp.float32)
    # both accumulate in f32; the kernel rounds its output to bf16
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=1e-2, atol=1e-1)


def test_window_layers_keep_a_ring_and_full_layers_the_whole_cache():
    from repro.models import init_cache

    cache = init_cache(SPEC.program_config(), 2, 48)
    lengths = [cache[f"pos{i}"]["attn"]["k"].shape[2] for i in range(4)]
    assert lengths == [8, 8, 8, 48]
