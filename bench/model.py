"""A configuration's file, the program's ModelConfig built from it, and the
weights: made on the device from the seed in one jitted call, in the type
they are served in, and laid out as the program's parameter tree."""

from __future__ import annotations

import dataclasses
import json
import pathlib
from typing import Any, Dict

import jax
import jax.numpy as jnp

from .flops import DenseShape

CONFIG_DIR = pathlib.Path(__file__).resolve().parent / "configs"

DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    name: str
    program: str            # the program's registry id
    reference: str          # module under bench/reference/
    qk_norm: bool
    rope_theta: float
    norm_eps: float
    tie_embeddings: bool
    dtype: Any
    shape: DenseShape
    slots: int
    max_len: int

    @classmethod
    def load(cls, name: str) -> "ModelSpec":
        path = CONFIG_DIR / f"{name}.json"
        raw = json.loads(path.read_text())
        return cls.from_dict(raw)

    @classmethod
    def from_dict(cls, raw: Dict[str, Any]) -> "ModelSpec":
        c = raw["config"]
        heads = int(c["num_attention_heads"])
        shape = DenseShape(
            layers=int(c["num_hidden_layers"]), d_model=int(c["hidden_size"]),
            n_heads=heads, n_kv=int(c["num_key_value_heads"]),
            head_dim=int(c.get("head_dim") or c["hidden_size"] // heads),
            d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]))
        return cls(name=raw["name"], program=raw["program"],
                   reference=raw["reference"],
                   qk_norm=bool(raw["architecture"]["qk_norm"]),
                   rope_theta=float(c["rope_theta"]),
                   norm_eps=float(c["rms_norm_eps"]),
                   tie_embeddings=bool(c["tie_word_embeddings"]),
                   dtype=DTYPES[c["torch_dtype"]], shape=shape,
                   slots=int(raw["serve"]["slots"]),
                   max_len=int(raw["serve"]["max_len"]))

    def program_config(self):
        """The program's own config for this model, with every size from the
        file: the program's other settings stay as it ships them."""
        from repro.configs import get_config

        if not self.tie_embeddings:
            raise ValueError(f"{self.name}: the program serves tied heads only")
        s = self.shape
        return dataclasses.replace(
            get_config(self.program), name=self.name, n_layers=s.layers,
            d_model=s.d_model, n_heads=s.n_heads, n_kv=s.n_kv,
            head_dim=s.head_dim, d_ff=s.d_ff, vocab=s.vocab,
            qk_norm=self.qk_norm, rope_theta=self.rope_theta,
            norm_eps=self.norm_eps, tie_embeddings=True, dtype=self.dtype)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def make_weights(spec: ModelSpec, key: jax.Array) -> Dict[str, Any]:
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
        if spec.qk_norm:
            attn["q_norm"] = norm((L, hd))
            attn["k_norm"] = norm((L, hd))
        layer = {"norm1": norm((L, d)), "norm2": norm((L, d)), "attn": attn,
                 "mlp": {"w_gate": normal((L, d, s.d_ff), d ** -0.5),
                         "w_up": normal((L, d, s.d_ff), d ** -0.5),
                         "w_down": normal((L, s.d_ff, d), s.d_ff ** -0.5)}}
        return {"embed": normal((round_up(s.vocab, 256), d), 0.02),
                "final_norm": norm((d,)), "layers": {"pos0": layer}}

    return jax.jit(build)(key)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole-number seed (larger than 32 bits too)."""
    from .traffic import seed_rng

    return jax.random.PRNGKey(int(seed_rng(seed, 0).integers(0, 2 ** 31)))
