"""Sparse-expert decoders whose layers mix sliding-window and full GQA
attention (Mellum 2 family): their sizes and work counts, the program's
config and the weights.

Reads from the configuration's file, at its top level as the published
``config.json`` gives them: ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``num_experts``, ``num_experts_per_tok``, ``moe_intermediate_size``,
``norm_topk_prob``, ``layer_types`` (``sliding_attention`` or
``full_attention`` per layer), ``mlp_layer_types`` (``sparse`` per layer),
``sliding_window``, ``rope_parameters`` (per layer kind: ``default``, or
``yarn`` with its factor, original context, betas and attention factor),
``rms_norm_eps``, ``vocab_size`` and ``tie_word_embeddings``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench.model import round_up

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class MoESWAShape:
    """The sizes of a sparse-expert, mixed-window decoder that the counts
    and the reference need."""

    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    n_experts: int
    top_k: int
    expert_ff: int
    vocab: int
    window: int
    layer_types: Tuple[str, ...]
    rope: Dict[str, Dict[str, Any]]     # rope_parameters, by layer type
    norm_eps: float

    @property
    def expert_layers(self) -> int:
        """Layers with experts: every one."""
        return self.layers

    def projections(self) -> List[Tuple[str, int, int]]:
        """(name, N, K) of each attention projection GEMM of one layer,
        whose rows are its span's rows.  The expert GEMMs' rows are the
        tokens routed to each expert: they are ``expert_gemms()``."""
        d, q, kv = (self.d_model, self.n_heads * self.head_dim,
                    self.n_kv * self.head_dim)
        return [("q", q, d), ("k", kv, d), ("v", kv, d), ("o", d, q)]

    def expert_gemms(self) -> List[Tuple[str, int, int]]:
        """(name, N, K) of each grouped expert GEMM of one layer: every
        expert's weight is (K, N), and its rows are the token copies
        routed to it."""
        d, f = self.d_model, self.expert_ff
        return [("gate", f, d), ("up", f, d), ("down", d, f)]

    def token_params(self) -> int:
        """Weights one token multiplies by in one layer: the projections,
        the router and its top-k experts."""
        proj = sum(n * k for _, n, k in self.projections())
        experts = self.top_k * sum(n * k for _, n, k in self.expert_gemms())
        return proj + self.d_model * self.n_experts + experts

    def context_sum(self, tokens: int, window: Optional[int]) -> int:
        """Positions attended over by positions 0..tokens-1: p + 1 each,
        or min(p + 1, window) in a window layer."""
        if window is None or tokens <= window:
            return tokens * (tokens + 1) // 2
        return window * (window + 1) // 2 + (tokens - window) * window

    def request_model_flops(self, prompt_len: int, decoded: int) -> float:
        """Model FLOPs that one request's live tokens require: the prompt's
        tokens in prefill and ``decoded`` tokens fed back through decode,
        each through the projections, the router and its top-k experts,
        attending over its real context (at most the window in a window
        layer), and the head for each position whose logits are sampled
        (the prompt's last and every decoded one)."""
        tokens = prompt_len + decoded
        per_pos = 4.0 * self.n_heads * self.head_dim    # 2 * (QK^T + PV)
        attn = sum(per_pos * self.context_sum(
            tokens, self.window if kind == SLIDING else None)
            for kind in self.layer_types)
        head = 2.0 * self.d_model * self.vocab * (1 + decoded)
        return 2.0 * self.layers * self.token_params() * tokens + attn + head


def shape(raw: Dict[str, Any]) -> MoESWAShape:
    kinds = tuple(raw["layer_types"])
    if len(kinds) != int(raw["num_hidden_layers"]):
        raise ValueError(f"{raw['name']}: {len(kinds)} layer types for "
                         f"{raw['num_hidden_layers']} layers")
    if set(raw["mlp_layer_types"]) != {"sparse"} or not raw["norm_topk_prob"]:
        raise ValueError(f"{raw['name']}: every MLP must be sparse, with "
                         "renormalised top-k weights")
    return MoESWAShape(
        layers=len(kinds), d_model=int(raw["hidden_size"]),
        n_heads=int(raw["num_attention_heads"]),
        n_kv=int(raw["num_key_value_heads"]), head_dim=int(raw["head_dim"]),
        n_experts=int(raw["num_experts"]),
        top_k=int(raw["num_experts_per_tok"]),
        expert_ff=int(raw["moe_intermediate_size"]),
        vocab=int(raw["vocab_size"]), window=int(raw["sliding_window"]),
        layer_types=kinds, rope=raw["rope_parameters"],
        norm_eps=float(raw["rms_norm_eps"]))


def _pattern(kinds: Tuple[str, ...]):
    """The program's (mixer, mlp) pattern: the shortest period of the
    layer types that repeats over all of them."""
    from repro.models import ATTN, ATTN_WINDOW, MOE_MLP

    mixer = {SLIDING: ATTN_WINDOW, FULL: ATTN}
    for n in range(1, len(kinds) + 1):
        if len(kinds) % n == 0 and kinds == kinds[:n] * (len(kinds) // n):
            return tuple((mixer[k], MOE_MLP) for k in kinds[:n])
    raise AssertionError(kinds)


def _yarn(rope: Dict[str, Any]):
    from repro.models.layers import YaRN

    if rope["rope_type"] == "default":
        return None
    if rope["rope_type"] != "yarn":
        raise ValueError(f"RoPE type {rope['rope_type']!r} is not served")
    return YaRN(factor=float(rope["factor"]),
                original_max_position=int(
                    rope["original_max_position_embeddings"]),
                beta_fast=float(rope["beta_fast"]),
                beta_slow=float(rope["beta_slow"]),
                attention_factor=float(rope["attention_factor"]))


def program_config(spec):
    """The program's own config for this model, with every size from the
    file: the program's other settings stay as it ships them."""
    from repro.configs import get_config

    s, raw = spec.shape, spec.raw
    if raw["tie_word_embeddings"]:
        raise ValueError(f"{spec.name}: this architecture's head is untied")
    window_rope, full_rope = s.rope[SLIDING], s.rope[FULL]
    if (_yarn(window_rope) is not None
            or window_rope["rope_theta"] != full_rope["rope_theta"]):
        raise ValueError(f"{spec.name}: window layers must use default RoPE "
                         "at the full layers' theta")
    return dataclasses.replace(
        get_config(spec.program), name=spec.name, n_layers=s.layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv=s.n_kv,
        head_dim=s.head_dim, d_ff=s.expert_ff, vocab=s.vocab,
        pattern=_pattern(s.layer_types), n_experts=s.n_experts,
        top_k=s.top_k, sliding_window=s.window,
        rope_theta=float(full_rope["rope_theta"]),
        rope_yarn=_yarn(full_rope), norm_eps=s.norm_eps,
        tie_embeddings=False, qk_norm=False, dtype=spec.dtype)


def make_weights(spec, key: jax.Array) -> Dict[str, Any]:
    """Random weights as the program's parameter tree, on the device:
    ``embed``, ``lm_head``, ``final_norm`` and per pattern position
    ``layers/pos{i}/{norm1, norm2, attn/{wq, wk, wv, wo},
    moe/{router, w_gate, w_up, w_down}}``, stacked over the repeats, the
    experts stacked (E, D, F) and (E, F, D), the router (D, E) in f32.

    Projections and experts are N(0, 1/fan_in), the router too (so its
    logits spread about one unit), the embedding and head N(0, 0.02^2)
    and norm scales 1 + N(0, 0.1^2), so that a dropped scale shows.  The
    tables have the program's padded row count; rows past the vocabulary
    are never read as tokens and their logits are not served."""
    s, dt = spec.shape, spec.dtype
    P = len(_pattern(s.layer_types))
    R = s.layers // P
    d, hd, E, F = s.d_model, s.head_dim, s.n_experts, s.expert_ff

    def build(key):
        keys = iter(jax.random.split(key, 16 * P + 8))

        def normal(shape, scale, dtype=dt):
            # drawn in the stored type: no float32 copy of a large table
            return jax.random.normal(next(keys), shape, dtype) * jnp.asarray(
                scale, dtype)

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dt)

        layers = {}
        for i in range(P):
            layers[f"pos{i}"] = {
                "norm1": norm((R, d)), "norm2": norm((R, d)),
                "attn": {"wq": normal((R, d, s.n_heads * hd), d ** -0.5),
                         "wk": normal((R, d, s.n_kv * hd), d ** -0.5),
                         "wv": normal((R, d, s.n_kv * hd), d ** -0.5),
                         "wo": normal((R, s.n_heads * hd, d),
                                      (s.n_heads * hd) ** -0.5)},
                "moe": {"router": normal((R, d, E), d ** -0.5, jnp.float32),
                        "w_gate": normal((R, E, d, F), d ** -0.5),
                        "w_up": normal((R, E, d, F), d ** -0.5),
                        "w_down": normal((R, E, F, d), F ** -0.5)}}
        rows = round_up(s.vocab, 256)
        return {"embed": normal((rows, d), 0.02),
                "lm_head": normal((rows, d), 0.02),
                "final_norm": norm((d,)), "layers": layers}

    return jax.jit(build)(key)
