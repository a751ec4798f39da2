"""Dense GQA decoders with a tied head (Llama / Qwen3 family): their sizes
and work counts, the program's config and the weights.

Reads from the configuration's file: ``architecture.qk_norm`` and the
published ``config`` keys ``num_hidden_layers``, ``hidden_size``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim`` (else
``hidden_size / num_attention_heads``), ``intermediate_size``,
``vocab_size``, ``rope_theta``, ``rms_norm_eps`` and
``tie_word_embeddings``."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from bench.model import round_up


@dataclasses.dataclass(frozen=True)
class DenseShape:
    """The sizes of a dense GQA decoder that the counts and the reference
    need."""

    layers: int
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    d_ff: int
    vocab: int
    qk_norm: bool
    rope_theta: float
    norm_eps: float

    def projections(self) -> List[Tuple[str, int, int]]:
        """(name, N, K) of each projection GEMM of one layer: x (M, K) @ W,
        M the rows of the span it runs in."""
        d, q, kv, f = (self.d_model, self.n_heads * self.head_dim,
                       self.n_kv * self.head_dim, self.d_ff)
        return [("q", q, d), ("k", kv, d), ("v", kv, d), ("o", d, q),
                ("gate", f, d), ("up", f, d), ("down", d, f)]

    def projection_params(self) -> int:
        """Projection weights of one layer."""
        return sum(n * k for _, n, k in self.projections())

    def attention_flops(self, context: int) -> float:
        """Scores and weighted sum of one token over ``context`` positions,
        all layers: 2 * (QK^T + PV) per head."""
        return 4.0 * self.layers * self.n_heads * self.head_dim * context

    def request_model_flops(self, prompt_len: int, decoded: int) -> float:
        """Model FLOPs that one request's live tokens require: the prompt's
        tokens in prefill and ``decoded`` tokens fed back through decode,
        each through every projection and attending over its real context,
        and the head for each position whose logits are sampled (the
        prompt's last and every decoded one)."""
        per_token = 2.0 * self.layers * self.projection_params()
        tokens = prompt_len + decoded
        # position p attends over p + 1 positions: sum over p < tokens
        context_sum = tokens * (tokens + 1) // 2
        head = 2.0 * self.d_model * self.vocab * (1 + decoded)
        return (per_token * tokens + self.attention_flops(1) * context_sum
                + head)


def shape(raw: Dict[str, Any]) -> DenseShape:
    c = raw["config"]
    heads = int(c["num_attention_heads"])
    return DenseShape(
        layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
        n_heads=heads, n_kv=int(c["num_key_value_heads"]),
        head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
        d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        qk_norm=bool(raw["architecture"]["qk_norm"]),
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]))


def program_config(spec):
    """The program's own config for this model, with every size from the
    file: the program's other settings stay as it ships them."""
    from repro.configs import get_config

    if not spec.raw["config"]["tie_word_embeddings"]:
        raise ValueError(f"{spec.name}: the program serves tied heads only")
    s = spec.shape
    return dataclasses.replace(
        get_config(spec.program), name=spec.name, n_layers=s.layers,
        d_model=s.d_model, n_heads=s.n_heads, n_kv=s.n_kv,
        head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
        qk_norm=s.qk_norm, rope_theta=s.rope_theta, norm_eps=s.norm_eps,
        tie_embeddings=True, dtype=spec.dtype)


def make_weights(spec, key: jax.Array) -> Dict[str, Any]:
    """Random weights as the program's parameter tree, on the device.

    Projections are N(0, 1/fan_in), the embedding N(0, 0.02^2) and norm
    scales 1 + N(0, 0.1^2), so that a dropped scale shows.  The embedding
    has the program's padded row count; rows past the vocabulary are never
    read as tokens and their logits are not served."""
    s, dt = spec.shape, spec.dtype
    L, d, hd = s.layers, s.d_model, s.head_dim

    def build(key):
        keys = iter(jax.random.split(key, 16))

        def normal(shape, scale):
            # drawn in the served type: no float32 copy of a large table
            return jax.random.normal(next(keys), shape, dt) * jnp.asarray(
                scale, dt)

        def norm(shape):
            return (1.0 + 0.1 * jax.random.normal(next(keys), shape,
                                                  jnp.float32)).astype(dt)

        attn = {"wq": normal((L, d, s.n_heads * hd), d ** -0.5),
                "wk": normal((L, d, s.n_kv * hd), d ** -0.5),
                "wv": normal((L, d, s.n_kv * hd), d ** -0.5),
                "wo": normal((L, s.n_heads * hd, d), (s.n_heads * hd) ** -0.5)}
        if s.qk_norm:
            attn["q_norm"] = norm((L, hd))
            attn["k_norm"] = norm((L, hd))
        layer = {"norm1": norm((L, d)), "norm2": norm((L, d)), "attn": attn,
                 "mlp": {"w_gate": normal((L, d, s.d_ff), d ** -0.5),
                         "w_up": normal((L, d, s.d_ff), d ** -0.5),
                         "w_down": normal((L, s.d_ff, d), s.d_ff ** -0.5)}}
        return {"embed": normal((round_up(s.vocab, 256), d), 0.02),
                "final_norm": norm((d,)), "layers": {"pos0": layer}}

    return jax.jit(build)(key)
