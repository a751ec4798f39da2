"""Plain float32 reference of a dense GQA decoder (Llama / Qwen3 family).

Pre-norm residual blocks: RMSNorm, attention with grouped K/V heads, RoPE
(rotate-half, the config's theta), optional RMSNorm of each query and key
head before RoPE (qk-norm, Qwen3), causal softmax; RMSNorm and a SwiGLU
MLP; a final RMSNorm and the head, tied to the embedding table.  Every
matmul runs in float32 at ``Precision.HIGHEST``.  Nothing here imports the
program: the weights come as a tree the benchmark made, under the names it
gave them.

It runs one sequence at a time, one layer at a time, queries in blocks and
the vocabulary in slices, so that it fits beside the served weights.

``fp8=True`` computes the same thing with every matmul operand (weights,
activations, keys and values) rounded to float8 e4m3 with a per-tensor
scale: the control, one precision step below the configuration's bf16.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
E4M3_MAX = 448.0
Q_BLOCK = 256
VOCAB_SLICE = 32768


def round_e4m3(x: jax.Array) -> jax.Array:
    """Round float32 to the nearest float8 e4m3 value (3 mantissa bits,
    normal exponents from -6, subnormal steps of 2^-9), saturating."""
    a = jnp.abs(x)
    e = jnp.floor(jnp.log2(jnp.maximum(a, 2.0 ** -9)))
    step = 2.0 ** (jnp.maximum(e, -6.0) - 3.0)
    return jnp.clip(jnp.round(x / step) * step, -E4M3_MAX, E4M3_MAX)


def to_fp8(x: jax.Array) -> jax.Array:
    """Per-tensor scaled e4m3 rounding, back in float32."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / E4M3_MAX
    return round_e4m3(x / scale) * scale


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv             # (S, d/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lw, *, spec, low):
    """One decoder layer over a whole sequence x (S, d), float32."""
    s = spec.shape
    q8 = to_fp8 if low else (lambda t: t)
    f32 = lambda t: t.astype(jnp.float32)
    mm = lambda a, w: jnp.dot(q8(a), q8(f32(w)), precision=HI)
    S = x.shape[0]
    pos = jnp.arange(S)
    h = _rms(x, f32(lw["norm1"]), s.norm_eps)
    a = lw["attn"]
    q = mm(h, a["wq"]).reshape(S, s.n_heads, s.head_dim)
    k = mm(h, a["wk"]).reshape(S, s.n_kv, s.head_dim)
    v = mm(h, a["wv"]).reshape(S, s.n_kv, s.head_dim)
    if s.qk_norm:
        q = _rms(q, f32(a["q_norm"]), s.norm_eps)
        k = _rms(k, f32(a["k_norm"]), s.norm_eps)
    q, k = _rope(q, pos, s.rope_theta), _rope(k, pos, s.rope_theta)
    q, k, v = q8(q), q8(k), q8(v)
    rep = s.n_heads // s.n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    outs = []
    for b in range(0, S, Q_BLOCK):
        qb = q[b:b + Q_BLOCK]
        sc = jnp.einsum("qhd,khd->hqk", qb, k, precision=HI) / np.sqrt(
            s.head_dim)
        mask = pos[None, b:b + Q_BLOCK, None] >= pos[None, None, :]
        p = jax.nn.softmax(jnp.where(mask, sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v, precision=HI))
    o = jnp.concatenate(outs, 0).reshape(S, s.n_heads * s.head_dim)
    x = x + mm(o, a["wo"])
    h = _rms(x, f32(lw["norm2"]), s.norm_eps)
    m = lw["mlp"]
    return x + mm(jax.nn.silu(mm(h, m["w_gate"])) * mm(h, m["w_up"]),
                  m["w_down"])


def _slice_rows(vocab_rows: int) -> int:
    """The largest multiple of 256 dividing the table that is at most
    VOCAB_SLICE rows, so that every slice has one shape."""
    best = 256
    for rows in range(256, min(vocab_rows, VOCAB_SLICE) + 1, 256):
        if vocab_rows % rows == 0:
            best = rows
    return best


class Reference:
    """Logits of a dense GQA decoder at chosen positions of one sequence.

    Sequences are padded at the end to ``length`` (one compiled program per
    cell): causal attention keeps the padding out of every real position.
    ``n_positions`` fixes how many positions one call reads."""

    def __init__(self, spec, weights: Dict[str, Any], length: int,
                 n_positions: int, fp8: bool = False) -> None:
        self.spec, self.w, self.length = spec, weights, length
        self.n_positions = n_positions
        low = bool(fp8)
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._layer = jax.jit(
            lambda x, layers, i: _layer(
                x, jax.tree_util.tree_map(
                    lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, False),
                    layers), spec=spec, low=low))
        rows = weights["embed"].shape[0]
        self._slice = _slice_rows(rows)
        eps = spec.shape.norm_eps

        @jax.jit
        def final(x, norm, pos):
            return _rms(x[pos], norm.astype(jnp.float32), eps)

        @functools.partial(jax.jit, static_argnums=2)
        def head(xf, table, rows):
            def one(i):
                w = jax.lax.dynamic_slice_in_dim(table, i * rows, rows, 0)
                w = w.astype(jnp.float32)
                if low:
                    w = round_e4m3(w / emax) * emax
                    return jnp.dot(xq, w.T, precision=HI)
                return jnp.dot(xf, w.T, precision=HI)

            emax = jnp.max(jnp.abs(table)).astype(jnp.float32) / E4M3_MAX
            xq = to_fp8(xf)
            return jnp.concatenate(
                [one(i) for i in range(table.shape[0] // rows)], axis=1)

        self._final, self._head = final, head

    def logits(self, tokens: np.ndarray, positions: np.ndarray) -> jax.Array:
        """(len(positions), vocab) float32 logits; ``positions`` index
        ``tokens``, at most ``n_positions`` of them."""
        n, p = len(tokens), len(positions)
        if n > self.length or p > self.n_positions:
            raise ValueError((n, self.length, p, self.n_positions))
        toks = np.zeros(self.length, np.int32)
        toks[:n] = tokens
        pos = np.full(self.n_positions, positions[-1], np.int32)
        pos[:p] = positions
        x = self._embed(self.w["embed"], jnp.asarray(toks))
        layers = self.w["layers"]["pos0"]
        for i in range(self.spec.shape.layers):
            x = self._layer(x, layers, i)
        xf = self._final(x, self.w["final_norm"], jnp.asarray(pos))
        out = self._head(xf, self.w["embed"], self._slice)
        return out[:p, : self.spec.shape.vocab]
