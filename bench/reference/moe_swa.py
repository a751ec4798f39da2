"""Plain float32 reference of a sparse-expert decoder whose layers mix
sliding-window and full GQA attention (Mellum 2 family).

Pre-norm residual blocks: RMSNorm; attention with grouped K/V heads and
rotate-half RoPE, causal, where a sliding-window layer's query at p sees
only keys p-W+1..p, and a full layer's RoPE is YaRN-scaled (inverse
frequencies blended between 1/theta^i and 1/(factor theta^i) over a linear
ramp between the dimensions where beta_fast and beta_slow rotations fit the
original context; cos and sin scaled by the attention factor); RMSNorm and
a sparse MLP: the router's f32 softmax over all experts, the top k,
renormalised, and each token's sum of its k experts' SwiGLU outputs
weighted so, with no capacity and no token dropped; a final RMSNorm and
the untied head.  Every matmul runs in float32 at ``Precision.HIGHEST``.
Nothing here imports the program: the weights come as a tree the benchmark
made, under the names it gave them.

It runs one sequence at a time, one layer at a time and one expert at a
time (every token through each expert, weighted by its routing weight,
zero where the expert is not among its top k), queries in blocks and the
vocabulary in slices, so that it fits beside the served weights.

``fp8=True`` computes the same thing with every matmul operand (weights,
activations, keys and values, the router's too) rounded to float8 e4m3
with a per-tensor scale: the control, one precision step below the
configuration's bf16.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference.dense_gqa import (E4M3_MAX, HI, Q_BLOCK, _rms,
                                       _slice_rows, round_e4m3, to_fp8)

SLIDING = "sliding_attention"


def inv_freq(head_dim: int, rope: Dict[str, Any]) -> np.ndarray:
    """RoPE inverse frequencies of one layer kind, and YaRN's when its
    ``rope_type`` is ``yarn``."""
    theta = float(rope["rope_theta"])
    base = theta ** (np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    if rope["rope_type"] == "default":
        return (1.0 / base).astype(np.float32)
    assert rope["rope_type"] == "yarn", rope
    orig = float(rope["original_max_position_embeddings"])

    def dim_of(rotations):
        return (head_dim * math.log(orig / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(dim_of(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(rope["beta_slow"]))), head_dim - 1)
    ramp = np.clip((np.arange(head_dim // 2) - low) / max(high - low, 1e-3),
                   0.0, 1.0)
    factor = float(rope["factor"])
    return ((1.0 - ramp) / base + ramp / (factor * base)).astype(np.float32)


def rope_scale(rope: Dict[str, Any]) -> float:
    return float(rope["attention_factor"]) if rope["rope_type"] == "yarn" \
        else 1.0


def _rope(x, pos, inv, scale):
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv)  # (S, d/2)
    cos = (jnp.cos(ang) * scale)[:, None]
    sin = (jnp.sin(ang) * scale)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _layer(x, lw, *, spec, kind, low):
    """One decoder layer over a whole sequence x (S, d), float32."""
    s = spec.shape
    q8 = to_fp8 if low else (lambda t: t)
    f32 = lambda t: t.astype(jnp.float32)
    mm = lambda a, w: jnp.dot(q8(a), q8(f32(w)), precision=HI)
    S = x.shape[0]
    pos = jnp.arange(S)
    rope = s.rope[kind]
    inv, scale = inv_freq(s.head_dim, rope), rope_scale(rope)
    window = s.window if kind == SLIDING else S
    h = _rms(x, f32(lw["norm1"]), s.norm_eps)
    a = lw["attn"]
    q = mm(h, a["wq"]).reshape(S, s.n_heads, s.head_dim)
    k = mm(h, a["wk"]).reshape(S, s.n_kv, s.head_dim)
    v = mm(h, a["wv"]).reshape(S, s.n_kv, s.head_dim)
    q, k = _rope(q, pos, inv, scale), _rope(k, pos, inv, scale)
    q, k, v = q8(q), q8(k), q8(v)
    rep = s.n_heads // s.n_kv
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)
    outs = []
    for b in range(0, S, Q_BLOCK):
        lo = max(0, b - window + 1)         # the block's first visible key
        hi = min(S, b + Q_BLOCK)
        sc = jnp.einsum("qhd,khd->hqk", q[b:hi], k[lo:hi],
                        precision=HI) / np.sqrt(s.head_dim)
        dist = pos[b:hi, None] - pos[None, lo:hi]
        mask = (dist >= 0) & (dist < window)
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, v[lo:hi], precision=HI))
    o = jnp.concatenate(outs, 0).reshape(S, s.n_heads * s.head_dim)
    x = x + mm(o, a["wo"])
    h = _rms(x, f32(lw["norm2"]), s.norm_eps)
    m = lw["moe"]
    probs = jax.nn.softmax(mm(h, m["router"]), axis=-1)      # (S, E)
    top, idx = jax.lax.top_k(probs, s.top_k)
    top = top / top.sum(-1, keepdims=True)
    weight = jnp.zeros_like(probs).at[pos[:, None], idx].set(top)

    def expert(e, acc):
        w = lambda name: jax.lax.dynamic_index_in_dim(m[name], e, 0, False)
        y = mm(jax.nn.silu(mm(h, w("w_gate"))) * mm(h, w("w_up")),
               w("w_down"))
        return acc + jax.lax.dynamic_index_in_dim(weight, e, 1) * y

    return x + jax.lax.fori_loop(0, s.n_experts, expert, jnp.zeros_like(x))


class Reference:
    """Logits of a sparse-expert, mixed-window decoder at chosen positions
    of one sequence.

    Sequences are padded at the end to ``length`` (one compiled program per
    layer kind and cell): causal attention keeps the padding out of every
    real position, and routing is per token.  ``n_positions`` fixes how
    many positions one call reads."""

    def __init__(self, spec, weights: Dict[str, Any], length: int,
                 n_positions: int, fp8: bool = False) -> None:
        self.spec, self.w, self.length = spec, weights, length
        self.n_positions = n_positions
        low = bool(fp8)
        self.period = len(weights["layers"])
        self._embed = jax.jit(lambda e, t: e[t].astype(jnp.float32))
        self._layer = {
            kind: jax.jit(
                lambda x, layers, i, kind=kind: _layer(
                    x, jax.tree_util.tree_map(
                        lambda w: jax.lax.dynamic_index_in_dim(w, i, 0,
                                                               False),
                        layers), spec=spec, kind=kind, low=low))
            for kind in set(spec.shape.layer_types)}
        self._slice = _slice_rows(weights["lm_head"].shape[0])
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
        for i, kind in enumerate(self.spec.shape.layer_types):
            layers = self.w["layers"][f"pos{i % self.period}"]
            x = self._layer[kind](x, layers, i // self.period)
        xf = self._final(x, self.w["final_norm"], jnp.asarray(pos))
        out = self._head(xf, self.w["lm_head"], self._slice)
        return out[:p, : self.spec.shape.vocab]
