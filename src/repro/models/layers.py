"""Shared model building blocks, pure JAX.

Every block routes its large GEMMs through ``repro.kernels.dispatch`` so that
on a real TPU the input-aware tuner (the paper's contribution) supplies the
kernel configuration, while under SPMD jit / the CPU dry-run the same call
lowers to plain XLA ops whose cost analysis reflects the true dataflow.

Conventions:
  * params are nested dicts of jax.Arrays (pytrees);
  * activations default to bfloat16, accumulation/normalization in fp32;
  * shapes follow (batch, seq, ...) with heads split as (..., n_heads, head_dim).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import dispatch

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, shape: Tuple[int, ...], dtype,
               fan_in: Optional[int] = None) -> jax.Array:
    fan_in = fan_in if fan_in is not None else shape[0]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# norm / rope
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


@dataclasses.dataclass(frozen=True)
class YaRN:
    """YaRN RoPE scaling, as transformers' ``_compute_yarn_parameters``
    defines it: the inverse frequencies blend from extrapolated (1/theta^i)
    to interpolated (1/(factor theta^i)) over a linear ramp between the
    dimensions where ``beta_fast`` and ``beta_slow`` rotations fit the
    original context, and cos and sin scale by ``attention_factor``."""

    factor: float
    original_max_position: int
    beta_fast: float
    beta_slow: float
    attention_factor: float

    def inv_freq(self, head_dim: int, theta: float) -> np.ndarray:
        def dim_of(rotations):
            return (head_dim * math.log(self.original_max_position
                                        / (rotations * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(dim_of(self.beta_fast)), 0)
        high = min(math.ceil(dim_of(self.beta_slow)), head_dim - 1)
        if low == high:
            high += 0.001
        i = np.arange(head_dim // 2, dtype=np.float32)
        ramp = np.clip((i - low) / (high - low), 0.0, 1.0)
        extrapolate = 1.0 - ramp
        base = theta ** (np.arange(0, head_dim, 2, dtype=np.float32)
                         / head_dim)
        return ((1.0 / (self.factor * base)) * ramp
                + (1.0 / base) * extrapolate).astype(np.float32)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               yarn: Optional[YaRN] = None) -> jax.Array:
    """x (B, S, H, D); positions (B, S) or (S,)."""
    d = x.shape[-1]
    freqs = (rope_freqs(d, theta) if yarn is None
             else jnp.asarray(yarn.inv_freq(d, theta)))        # (d/2,)
    if positions.ndim == 1:
        positions = positions[None, :]
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    if yarn is not None:
        cos, sin = cos * yarn.attention_factor, sin * yarn.attention_factor
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# GQA attention (train/prefill path: chunked flash-style in pure jnp so that
# the 32k-seq dry-run never materializes an (S, S) score tensor)
# ---------------------------------------------------------------------------

def init_attention(key: jax.Array, d_model: int, n_heads: int, n_kv: int,
                   head_dim: int, qk_norm: bool, dtype) -> Params:
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d_model, n_heads * head_dim), dtype),
        "wk": dense_init(ks[1], (d_model, n_kv * head_dim), dtype),
        "wv": dense_init(ks[2], (d_model, n_kv * head_dim), dtype),
        "wo": dense_init(ks[3], (n_heads * head_dim, d_model), dtype,
                         fan_in=n_heads * head_dim),
    }
    if qk_norm:
        p["q_norm"] = jnp.ones((head_dim,), dtype)
        p["k_norm"] = jnp.ones((head_dim,), dtype)
    return p


def _chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                       causal: bool, q_start: jax.Array | int,
                       kv_len: Optional[jax.Array] = None,
                       chunk: int = 1024, unroll: bool = False) -> jax.Array:
    """Flash-style attention in pure jnp: scan over KV chunks with running
    (max, sum) so peak memory is O(S * chunk), not O(S^2).

    q (B, Sq, H, D); k/v (B, Skv, G, D) with G = kv heads; H % G == 0.
    GQA K/V are expanded to the full H heads *inside* each chunk step (an
    O(chunk)-sized gather) so every live tensor carries a flat H dimension —
    the layout head-TP sharding propagates through cleanly.
    q_start: absolute position of q[0] (for causal masking during decode).
    kv_len: number of valid kv positions (B,) or scalar; None = all valid.
    """
    B, Sq, H, D = q.shape
    Skv, G = k.shape[1], k.shape[2]
    rep = H // G
    scale = 1.0 / math.sqrt(D)
    chunk = min(chunk, Skv)
    n_chunks = -(-Skv // chunk)
    pad = n_chunks * chunk - Skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (n_chunks, B, chunk, G, D)
    kc = k.reshape(B, n_chunks, chunk, G, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, n_chunks, chunk, G, D).transpose(1, 0, 2, 3, 4)

    qf = q.astype(jnp.float32) * scale                   # (B, Sq, H, D)
    # q_start may be scalar or per-batch (B,) — normalize to (B or 1, Sq)
    q_pos = jnp.asarray(q_start).reshape(-1, 1) + jnp.arange(Sq)[None, :]
    valid_len = Skv if kv_len is None else kv_len

    def body(carry, inp):
        m, l, acc = carry                     # running max / sum / out
        kb, vb, c_idx = inp                   # (B, chunk, G, D)
        kb = jnp.repeat(kb, rep, axis=2).astype(jnp.float32)  # (B,ck,H,D)
        vb = jnp.repeat(vb, rep, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb)        # (B,H,Sq,chunk)
        kv_pos = c_idx * chunk + jnp.arange(chunk)       # (chunk,)
        mask = kv_pos[None, None, :] < jnp.asarray(valid_len).reshape(-1, 1, 1)
        if causal:
            mask = mask & (kv_pos[None, None, :] <= q_pos[:, :, None])
        s = jnp.where(mask[:, None, :, :], s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l = l * corr + p.sum(axis=-1)
        acc = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        return (m_new, l, acc), None

    m0 = jnp.full((B, H, Sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, H, Sq), jnp.float32)
    a0 = jnp.zeros((B, H, Sq, D), jnp.float32)
    # checkpoint the chunk body: without it, scan's backward stacks every
    # chunk's score matrix — silently re-materializing the full O(S^2) buffer
    # the chunking exists to avoid.
    (m, l, acc), _ = jax.lax.scan(
        jax.checkpoint(body), (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)),
        unroll=bool(unroll))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = out.transpose(0, 2, 1, 3)                      # (B, Sq, H, D)
    return out.astype(q.dtype)


def _block_causal_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                            chunk: int = 1024,
                            unroll: bool = False) -> jax.Array:
    """Causal self-attention that SKIPS upper-triangular blocks (hillclimb
    H-series; see EXPERIMENTS.md §Perf).

    The plain chunked scan computes every (q, kv-chunk) pair and masks the
    future — half the score FLOPs are discarded.  Here both axes are chunked
    and a single scan walks only the nq*(nq+1)/2 lower-triangular block
    pairs (a static list), updating that q-chunk's running (max, sum, acc)
    in place.  Same math, ~2x fewer attention FLOPs at full sequence length.

    Requires Sq == Skv, q_start == 0, full validity (the training/prefill
    self-attention case); callers fall back to _chunked_attention otherwise.
    """
    B, Sq, H, D = q.shape
    G = k.shape[2]
    rep = H // G
    scale = 1.0 / math.sqrt(D)
    ck = min(chunk, Sq)
    assert Sq % ck == 0, (Sq, ck)
    nq = Sq // ck
    qc = (q.astype(jnp.float32) * scale).reshape(B, nq, ck, H, D
                                                 ).transpose(1, 0, 2, 3, 4)
    kc = k.reshape(B, nq, ck, G, D).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(B, nq, ck, G, D).transpose(1, 0, 2, 3, 4)

    pairs = [(qi, ki) for qi in range(nq) for ki in range(qi + 1)]
    qis = jnp.asarray([p[0] for p in pairs])
    kis = jnp.asarray([p[1] for p in pairs])

    def body(carry, inp):
        m, l, acc = carry          # (nq, B, H, ck) / ... / (nq, B, H, ck, D)
        qi, ki = inp
        qb = jax.lax.dynamic_index_in_dim(qc, qi, 0, keepdims=False)
        kb = jnp.repeat(jax.lax.dynamic_index_in_dim(kc, ki, 0, False),
                        rep, axis=2).astype(jnp.float32)
        vb = jnp.repeat(jax.lax.dynamic_index_in_dim(vc, ki, 0, False),
                        rep, axis=2).astype(jnp.float32)
        s = jnp.einsum("bqhd,bkhd->bhqk", qb, kb)
        # mask only the diagonal block (qi == ki); earlier blocks are fully
        # visible, later ones never computed
        tri = jnp.tril(jnp.ones((ck, ck), bool))
        s = jnp.where((qi != ki) | tri[None, None], s, -1e30)
        mi = jax.lax.dynamic_index_in_dim(m, qi, 0, False)
        li = jax.lax.dynamic_index_in_dim(l, qi, 0, False)
        ai = jax.lax.dynamic_index_in_dim(acc, qi, 0, False)
        m_new = jnp.maximum(mi, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(mi - m_new)
        li = li * corr + p.sum(axis=-1)
        ai = ai * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vb)
        m = jax.lax.dynamic_update_index_in_dim(m, m_new, qi, 0)
        l = jax.lax.dynamic_update_index_in_dim(l, li, qi, 0)
        acc = jax.lax.dynamic_update_index_in_dim(acc, ai, qi, 0)
        return (m, l, acc), None

    m0 = jnp.full((nq, B, H, ck), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((nq, B, H, ck), jnp.float32)
    a0 = jnp.zeros((nq, B, H, ck, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body), (m0, l0, a0),
                                  (qis, kis), unroll=bool(unroll))
    out = acc / jnp.maximum(l[..., None], 1e-30)
    # (nq, B, H, ck, D) -> (B, Sq, H, D)
    out = out.transpose(1, 0, 3, 2, 4).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


def _masked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      q_pos: jax.Array, kv_pos: jax.Array,
                      window: int) -> jax.Array:
    """Attention of q (B, Sq, H, D) over k/v (B, L, G, D) whose keys sit at
    ``kv_pos`` (B, L) (negative: no key): a query at ``q_pos`` (B, Sq)
    attends to keys with 0 <= q_pos - kv_pos < window.  Scores in f32,
    contracted per KV group."""
    B, Sq, H, D = q.shape
    G = k.shape[2]
    qf = q.astype(jnp.float32).reshape(B, Sq, G, H // G, D) / math.sqrt(D)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, k.astype(jnp.float32))
    dist = q_pos[:, :, None] - kv_pos[:, None, :]             # (B, Sq, L)
    mask = (kv_pos[:, None, :] >= 0) & (dist >= 0) & (dist < window)
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrqk,bkgd->bqgrd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, D).astype(q.dtype)


def _band_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, window: int,
                    block: int, unroll: bool = False) -> jax.Array:
    """Windowed attention over a whole step whose work is bounded by the
    band, not by the keys' length: q (B, S, H, D) are the last S of the
    P + S keys k/v (B, P + S, G, D), so query i's own key is key P + i.
    Queries go in blocks of ``block``, each against the block + window - 1
    keys its window can reach."""
    B, S = q.shape[:2]
    P = k.shape[1] - S
    block = min(block, S)
    n = -(-S // block)
    front = max(window - 1 - P, 0)          # keys before the first window
    back = n * block - S
    pad = lambda t, f, b, val=0: jnp.pad(
        t, [(0, 0), (f, b)] + [(0, 0)] * (t.ndim - 2), constant_values=val)
    kx, vx = pad(k, front, back), pad(v, front, back)
    px = pad(kv_pos, front, back, -1)
    qx, qpx = pad(q, 0, back), pad(q_pos, 0, back)
    span = block + window - 1
    first = P + front - (window - 1)        # keys of block 0 start here

    def one(i):
        at = i * block
        sl = lambda t, start, size: jax.lax.dynamic_slice_in_dim(
            t, start, size, axis=1)
        return _masked_attention(
            sl(qx, at, block), sl(kx, first + at, span),
            sl(vx, first + at, span), sl(qpx, at, block),
            sl(px, first + at, span), window)

    if unroll or n == 1:
        out = jnp.concatenate([one(i) for i in range(n)], axis=1)
    else:                                   # (n, B, block, H, D)
        out = jnp.moveaxis(jax.lax.map(one, jnp.arange(n)), 0, 1).reshape(
            B, n * block, *q.shape[2:])
    return out[:, :S]


def _window_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      cache: Optional[Params], cache_index, window: int,
                      unroll: bool = False
                      ) -> Tuple[jax.Array, Optional[Params]]:
    """Sliding-window attention (a query at p sees keys p-window+1..p).

    With a cache, the cache is a ring of Lr = min(window, max_len)
    positions per sequence, position p at p % Lr: a step writes its last
    min(S, Lr) keys there.  A one-token step attends over the ring itself;
    a longer one over the ring as it was, in position order, followed by
    its own keys, in blocks of half the window."""
    B, S = q.shape[:2]
    block = max(window // 2, 1)
    if cache is None:
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        return _band_attention(q, k, v, pos, pos, window, block,
                               unroll), None
    ring_k, ring_v = cache["k"], cache["v"]
    Lr = ring_k.shape[1]
    idx = jnp.broadcast_to(jnp.asarray(cache_index).reshape(-1), (B,))
    q_pos = idx[:, None] + jnp.arange(S)[None]               # (B, S)
    m = min(S, Lr)
    rows = jnp.arange(B)[:, None]
    cols = q_pos[:, S - m:] % Lr
    new = {"k": ring_k.at[rows, cols].set(k[:, S - m:].astype(ring_k.dtype)),
           "v": ring_v.at[rows, cols].set(v[:, S - m:].astype(ring_v.dtype))}
    j = jnp.arange(Lr)[None]
    if S == 1:
        # the latest position of each ring entry, up to this step's own
        last = idx[:, None]
        kv_pos = last - (last - j) % Lr
        return _masked_attention(q, new["k"], new["v"], q_pos, kv_pos,
                                 window), new
    old = idx[:, None] - Lr + j                               # (B, Lr)
    take = lambda t: jnp.take_along_axis(
        t, (old % Lr)[:, :, None, None], axis=1)
    keys = jnp.concatenate([take(ring_k), k.astype(ring_k.dtype)], axis=1)
    vals = jnp.concatenate([take(ring_v), v.astype(ring_v.dtype)], axis=1)
    kv_pos = jnp.concatenate([old, q_pos], axis=1)
    return _band_attention(q, keys, vals, q_pos, kv_pos, window, block,
                           unroll), new


def attention(p: Params, x: jax.Array, *, n_heads: int, n_kv: int,
              head_dim: int, positions: jax.Array, causal: bool,
              rope_theta: float, qk_norm: bool, norm_eps: float,
              cache: Optional[Params] = None,
              cache_index: Optional[jax.Array] = None,
              memory: Optional[jax.Array] = None,
              attn_chunk: int = 1024,
              decode_kv_splits: int = 1,
              unroll: bool = False,
              causal_block_skip: bool = False,
              window: int = 0,
              yarn: Optional[YaRN] = None,
              ) -> Tuple[jax.Array, Optional[Params]]:
    """GQA attention block body (no residual / pre-norm — caller owns those).

    cache: {'k': (B, L_max, G, D), 'v': ...} decode KV cache; cache_index is
    the number of tokens already in it.  memory: encoder output for
    cross-attention (whisper decoder) — keys/values come from memory instead
    of x, no cache, no causal mask.  window > 0: sliding-window causal
    self-attention, whose cache is a ring of ``window`` positions
    (``_window_attention``).  yarn: RoPE with YaRN scaling.
    """
    from repro.parallel import sharding as shd
    B, S, _ = x.shape
    q = dispatch.matmul2(x, p["wq"]).reshape(B, S, n_heads, head_dim)
    kv_src = memory if memory is not None else x
    Skv_in = kv_src.shape[1]
    k = dispatch.matmul2(kv_src, p["wk"]).reshape(B, Skv_in, n_kv, head_dim)
    v = dispatch.matmul2(kv_src, p["wv"]).reshape(B, Skv_in, n_kv, head_dim)

    # Attention TP placement.  Preferred: Megatron head-TP — q sharded over
    # heads, K/V gathered over 'model' (small, no quadratic term), the score
    # and PV work split by head, and wo contracting a head-sharded input so
    # the projection weights stay TP-resident.  Fallback when H % tp != 0
    # (smollm 9H, qwen3 40H, arctic 56H, whisper 8H): sequence-parallel
    # queries — the quadratic work splits by query position instead, at the
    # cost of gathering attention projection weights.
    # Applies to training AND prefill (S > 1, cache being filled): without
    # it GSPMD replicates the 32k x 32k score work across the model axis for
    # non-head-divisible archs (16x redundancy — EXPERIMENTS.md §Perf H4).
    tp = shd.axis_size("model")
    if tp > 1 and S > 1:
        if n_heads % tp == 0:
            q = shd.constrain(q, "batch", "none", "model", "none")
        elif S % tp == 0:
            q = shd.constrain(q, "batch", "seq", "none", "none")
        k = shd.constrain(k, "batch", "none", "none", "none")
        v = shd.constrain(v, "batch", "none", "none", "none")

    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)

    if memory is None:
        q = apply_rope(q, positions, rope_theta, yarn)
        kv_positions = positions
        k = apply_rope(k, kv_positions, rope_theta, yarn)

    if window:
        out, new_cache = _window_attention(q, k, v, cache, cache_index,
                                           window, unroll=unroll)
        return _out_proj(p, out, decode=cache is not None and S == 1), \
            new_cache

    new_cache = None
    kv_len = None
    if cache is not None:
        idx = jnp.asarray(cache_index)
        if idx.ndim == 0:
            k_full = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k.astype(cache["k"].dtype), idx, axis=1)
            v_full = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v.astype(cache["v"].dtype), idx, axis=1)
        else:
            # per-sequence cache positions (continuous batching slots)
            rows = jnp.arange(B)[:, None]
            cols = idx[:, None] + jnp.arange(S)[None, :]
            k_full = cache["k"].at[rows, cols].set(
                k.astype(cache["k"].dtype), mode="drop")
            v_full = cache["v"].at[rows, cols].set(
                v.astype(cache["v"].dtype), mode="drop")
        new_cache = {"k": k_full, "v": v_full}
        k, v = k_full, v_full
        kv_len = idx + S
        q_start = idx
    else:
        q_start = 0

    n_splits = 0
    if cache is not None and S == 1 and decode_kv_splits > 1:
        # long-context decode: sequence-parallel flash-decoding (SP).
        # The split count routes through the tuned attention space (this
        # runs at trace time, so the resolver's telemetry record is
        # captured by the engine like any kernel call); the caller's
        # decode_kv_splits is the heuristic fallback when nothing tuned
        # resolves, so untuned processes behave exactly as before.
        from repro.serve.flash_decode import (flash_decode_attention,
                                              resolve_decode_splits)
        n_splits = resolve_decode_splits(
            B=B, Hq=n_heads, Hkv=n_kv, Lkv=k.shape[1], D=head_dim,
            dtype_bits=dispatch._dtype_bits(q.dtype), causal=int(causal),
            default=decode_kv_splits)
        if n_splits <= 1 or k.shape[1] % n_splits != 0:
            n_splits = 0                 # untiled split: dense decode path
    if n_splits > 1:
        out = flash_decode_attention(q, k, v, kv_len, n_splits=n_splits)
    elif causal_block_skip and causal and memory is None and cache is None \
            and q.shape[1] == k.shape[1] \
            and q.shape[1] % min(attn_chunk, q.shape[1]) == 0:
        out = _block_causal_attention(q, k, v, chunk=attn_chunk,
                                      unroll=unroll)
    else:
        out = _chunked_attention(q, k, v, causal=causal and memory is None,
                                 q_start=q_start, kv_len=kv_len,
                                 chunk=attn_chunk, unroll=unroll)
    return _out_proj(p, out, decode=cache is not None and S == 1), new_cache


def _out_proj(p: Params, out: jax.Array, decode: bool) -> jax.Array:
    """wo over the heads' outputs (B, S, H, D)."""
    from repro.parallel import sharding as shd
    B, S = out.shape[:2]
    out = out.reshape(B, S, -1)
    if decode:
        # decode: wo's contraction dim (H*hd) is 'model'-sharded — pin the
        # attention output to match so wo is consumed in place (psum) rather
        # than gathered, and pin wo's OUTPUT D-sharded over 'data' likewise
        # (see ModelConfig.decode_replicate_acts)
        out = shd.constrain(out, "none", "none", "model")
        proj = dispatch.matmul2(out, p["wo"])
        return shd.constrain(proj, "none", "none", "fsdp")
    return dispatch.matmul2(out, p["wo"])


# ---------------------------------------------------------------------------
# dense SwiGLU MLP
# ---------------------------------------------------------------------------

def init_mlp(key: jax.Array, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": dense_init(ks[0], (d_model, d_ff), dtype),
        "w_up": dense_init(ks[1], (d_model, d_ff), dtype),
        "w_down": dense_init(ks[2], (d_ff, d_model), dtype, fan_in=d_ff),
    }


def mlp(p: Params, x: jax.Array, tp: bool = True) -> jax.Array:
    from repro.parallel import sharding as shd
    g = dispatch.matmul2(x, p["w_gate"])
    u = dispatch.matmul2(x, p["w_up"])
    if not tp:
        # pure-SP: tokens stay sequence-sharded, weights are consumed
        # replicated (dp_only rules) — zero activation reshards
        g = shd.constrain(g, "batch", "seq", "none")
        u = shd.constrain(u, "batch", "seq", "none")
        return dispatch.matmul2(jax.nn.silu(g) * u, p["w_down"])
    # Megatron TP: pin the hidden activations to the 'model' axis so the
    # ffn weights are consumed in their TP-sharded layout (all-gather x over
    # S, psum after w_down) instead of GSPMD electing to gather weights.
    # Decode (S == 1): keep batch unconstrained — feature-sharded decode
    # activations contract against the FSDP weight shards with tiny psums,
    # and forcing batch sharding here would reintroduce weight gathers.
    if x.shape[1] == 1:
        g = shd.constrain(g, "none", "none", "model")
        u = shd.constrain(u, "none", "none", "model")
        out = dispatch.matmul2(jax.nn.silu(g) * u, p["w_down"])
        # pin the output D-sharded over 'data' as well — otherwise GSPMD
        # prefers replicating the output and gathering w_down's D shards
        return shd.constrain(out, "none", "none", "fsdp")
    g = shd.constrain(g, "batch", "none", "model")
    u = shd.constrain(u, "batch", "none", "model")
    return dispatch.matmul2(jax.nn.silu(g) * u, p["w_down"])
