"""Per-architecture smoke tests: every assigned arch instantiates a REDUCED
config, runs one forward + one train step on CPU, asserts output shapes and
finiteness (spec deliverable f)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_NAMES, smoke_config, get_config
from repro.configs.shapes import SHAPES, applicable
from repro.models import (ModelConfig, decode_step, forward, init_cache,
                          init_params, loss_fn, prefill)
from repro.optim import AdamWConfig, adamw_init, adamw_update


def _batch(cfg, B=2, S=32, seed=0):
    rng = np.random.default_rng(seed)
    batch = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab, (B, S)), jnp.int32)}
    if cfg.frontend == "vision":
        batch["patch_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_frontend_tokens, cfg.d_model)),
            cfg.dtype)
    if cfg.is_encdec:
        batch["encoder_embeds"] = jnp.asarray(
            rng.normal(size=(B, cfg.encoder_len, cfg.d_model)), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", ARCH_NAMES)
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    B, S = batch["tokens"].shape

    x, aux = forward(params, cfg, batch)
    exp_S = S + (cfg.n_frontend_tokens if cfg.frontend == "vision" else 0)
    assert x.shape == (B, exp_S, cfg.d_model)
    assert np.isfinite(np.asarray(x, np.float32)).all()

    opt_cfg = AdamWConfig(lr=1e-3)
    opt = adamw_init(params, opt_cfg)
    (loss, metrics), grads = jax.value_and_grad(
        loss_fn, has_aux=True)(params, cfg, batch)
    assert np.isfinite(float(loss))
    new_params, opt, om = adamw_update(params, grads, opt, opt_cfg)
    # params actually moved
    delta = sum(float(jnp.abs(a - b).sum()) for a, b in zip(
        jax.tree_util.tree_leaves(new_params),
        jax.tree_util.tree_leaves(params)))
    assert delta > 0 and np.isfinite(float(om["grad_norm"]))


@pytest.mark.parametrize("arch", ["glm4-9b", "jamba-v0.1-52b",
                                  "mamba2-1.3b", "dbrx-132b",
                                  "mellum2-12b-a2.5b"])
def test_smoke_decode_matches_forward(arch):
    """prefill + decode == teacher-forced forward, per family."""
    import dataclasses
    # capacity_factor high enough that the training path drops no tokens:
    # MoE inference (decode path) is dropless by construction, so exact
    # train/decode agreement only holds in the no-drop regime.
    cfg = dataclasses.replace(smoke_config(arch), remat=False,
                              capacity_factor=8.0)
    params = init_params(cfg, jax.random.PRNGKey(0))
    toks = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 12)), jnp.int32)
    x, _ = forward(params, cfg, {"tokens": toks})
    head = params["embed" if cfg.tie_embeddings else "lm_head"]
    logits_fwd = jnp.einsum("bsd,vd->bsv", x, head)[..., : cfg.vocab]
    cache = init_cache(cfg, 2, 16)
    lg, cache = prefill(params, cfg, {"tokens": toks[:, :8]}, cache)
    np.testing.assert_allclose(np.asarray(lg[:, :cfg.vocab]),
                               np.asarray(logits_fwd[:, 7]),
                               rtol=5e-2, atol=5e-2)
    for t in range(8, 12):
        lg, cache = decode_step(params, cfg, toks[:, t:t + 1], cache,
                                jnp.asarray(t))
        np.testing.assert_allclose(np.asarray(lg[:, :cfg.vocab]),
                                   np.asarray(logits_fwd[:, t]),
                                   rtol=5e-2, atol=5e-2)


def test_whisper_decode_with_memory():
    cfg = smoke_config("whisper-base")
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    from repro.models import encode
    memory = encode(cfg, params, batch["encoder_embeds"])
    cache = init_cache(cfg, 2, 16)
    lg, cache = prefill(params, cfg, batch | {"tokens": batch["tokens"][:, :8]},
                        cache)
    lg2, _ = decode_step(params, cfg, batch["tokens"][:, 8:9], cache,
                         jnp.asarray(8), memory=memory)
    assert lg2.shape == (2, cfg.padded_vocab)
    assert np.isfinite(np.asarray(lg2)).all()


def test_param_count_matches_init():
    """ModelConfig.param_count (the 6ND accounting) must agree with the
    actual initialized tree."""
    for arch in ["smollm-135m", "dbrx-132b", "mamba2-1.3b",
                 "jamba-v0.1-52b", "whisper-base"]:
        cfg = smoke_config(arch)
        params = init_params(cfg, jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape))
                     for l in jax.tree_util.tree_leaves(params))
        predicted = cfg.param_count
        assert abs(actual - predicted) / actual < 0.05, (
            arch, actual, predicted)


def test_full_config_dims_are_exact():
    """The full (dry-run) configs carry exactly the assigned dimensions."""
    spec = {
        "dbrx-132b": (40, 6144, 48, 8, 10752, 100352),
        "arctic-480b": (35, 7168, 56, 8, 4864, 32000),
        "internvl2-76b": (80, 8192, 64, 8, 28672, 128256),
        "qwen3-14b": (40, 5120, 40, 8, 17408, 151936),
        "smollm-135m": (30, 576, 9, 3, 1536, 49152),
        "llama3-405b": (126, 16384, 128, 8, 53248, 128256),
        "glm4-9b": (40, 4096, 32, 2, 13696, 151552),
        "whisper-base": (6, 512, 8, 8, 2048, 51865),
        "mamba2-1.3b": (48, 2048, 0, 0, 0, 50280),
        "jamba-v0.1-52b": (32, 4096, 32, 8, 14336, 65536),
    }
    for arch, (L, d, h, kv, ff, v) in spec.items():
        cfg = get_config(arch)
        assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv,
                cfg.d_ff, cfg.vocab) == (L, d, h, kv, ff, v), arch


def test_applicability_rules():
    runnable = {a: [s for s in SHAPES
                    if applicable(get_config(a), s)[0]]
                for a in ARCH_NAMES}
    # long_500k only for SSM/hybrid
    assert "long_500k" in runnable["mamba2-1.3b"]
    assert "long_500k" in runnable["jamba-v0.1-52b"]
    assert "long_500k" not in runnable["llama3-405b"]
    # every arch runs the other three
    for a in ARCH_NAMES:
        assert {"train_4k", "prefill_32k", "decode_32k"} <= set(runnable[a])
    total = sum(len(v) for v in runnable.values())
    assert total == 35          # 44 cells - 9 rule-skipped
