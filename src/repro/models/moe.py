"""Mixture-of-Experts layer (dbrx / arctic / jamba).

Four execution paths, chosen by the layer wrapper:

  moe_dropless() — served path (prefill and decode, no mesh): every token
                 reaches its top-k experts, with no capacity.  The token
                 copies are sorted by expert and run as grouped GEMMs whose
                 rows per expert are only known at run time
                 (``dispatch.grouped_matmul``), then unsorted and combined.
  moe()        — training path without a mesh: the argsort+scatter
                 capacity dispatch, pure jnp.
  moe_ep()     — production expert-parallel path via shard_map: experts are
                 sharded over the 'model' mesh axis; activations arrive
                 batch-sharded and model-replicated, so dispatch is a purely
                 LOCAL gather/scatter into each device's own expert buffers,
                 expert FFNs run on local weights, and the only communication
                 is one psum over 'model' to combine expert outputs — the
                 same wire cost as a TP MLP all-reduce.  This is the
                 jax-native mapping of the GShard/Switch all-to-all pattern
                 (DESIGN.md §6): GSPMD cannot shard a data-dependent scatter
                 on its own, so the EP structure is made explicit.
  moe_decode() — decode path under a mesh: every expert runs on every token
                 (dense einsum over the expert axis, EP-sharded by GSPMD) and
                 a sparse (T, E) weight matrix combines — no gathers of
                 expert weight slabs, which would defeat EP sharding.

The expert FFN is three batched rectangular GEMMs (E, C, D) x (E, D, F):
exactly the small-irregular GEMM regime the paper's input-aware tuner
targets (DESIGN.md §5).
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.kernels import dispatch

from .layers import Params, dense_init


def init_moe(key: jax.Array, d_model: int, d_ff: int, n_experts: int,
             dtype) -> Params:
    ks = jax.random.split(key, 4)
    return {
        "router": dense_init(ks[0], (d_model, n_experts), jnp.float32),
        "w_gate": dense_init(ks[1], (n_experts, d_model, d_ff), dtype,
                             fan_in=d_model),
        "w_up": dense_init(ks[2], (n_experts, d_model, d_ff), dtype,
                           fan_in=d_model),
        "w_down": dense_init(ks[3], (n_experts, d_ff, d_model), dtype,
                             fan_in=d_ff),
    }


def _route(router_logits: jax.Array, top_k: int
           ) -> Tuple[jax.Array, jax.Array]:
    """(T, E) -> (weights (T, k), expert ids (T, k)); weights renormalized."""
    probs = jax.nn.softmax(router_logits.astype(jnp.float32), axis=-1)
    w, idx = jax.lax.top_k(probs, top_k)
    w = w / jnp.maximum(w.sum(-1, keepdims=True), 1e-9)
    return w, idx


def _aux_loss(logits: jax.Array, idx: jax.Array, n_experts: int) -> jax.Array:
    """Switch load-balancing loss: E * sum_e f_e * p_e."""
    me = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).mean(
        axis=tuple(range(logits.ndim - 1)))
    fe = jax.nn.one_hot(idx[..., 0], n_experts).mean(
        axis=tuple(range(idx.ndim - 1)))
    return (n_experts * jnp.sum(me * fe)).astype(jnp.float32)


def _capacity(S: int, top_k: int, n_experts: int, cf: float) -> int:
    return max(int(math.ceil(S * top_k * cf / n_experts)), 1)


def _dispatch_row(x_row, w_row, idx_row, *, n_experts: int, top_k: int,
                  C: int, e_first: int, e_count: int):
    """One sequence row -> (buffers (e_count, C, D), combine metadata).

    Slot-major formulation: all O(D)-wide intermediates are sized by the
    local expert capacity (e_count*C), never by S*top_k — the token->slot
    permutation is computed on integer vectors and then applied as ONE
    gather of shape (e_count*C, D).  (A token-major x_row[tok] gather would
    materialize an S*top_k x D buffer — 4x the activations, and 16x wasted
    on an EP device that only owns 1/16th of the experts.)"""
    S, D = x_row.shape
    k = top_k
    flat_e = idx_row.reshape(S * k)
    flat_t = jnp.repeat(jnp.arange(S), k)
    flat_w = w_row.reshape(S * k)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=n_experts)
    start = jnp.cumsum(counts) - counts
    pos = jnp.arange(S * k) - start[sorted_e]               # slot in expert
    local = (sorted_e >= e_first) & (sorted_e < e_first + e_count)
    keep = (pos < C) & local
    slot = jnp.where(keep, (sorted_e - e_first) * C + pos, e_count * C)
    tok = flat_t[order]
    # invert: which token (and weight) fills each local slot
    n_slots = e_count * C
    slot_tok = jnp.zeros((n_slots + 1,), jnp.int32).at[slot].set(
        tok.astype(jnp.int32), mode="drop")[:-1]
    slot_w = jnp.zeros((n_slots + 1,), jnp.float32).at[slot].set(
        flat_w[order], mode="drop")[:-1]
    slot_valid = jnp.zeros((n_slots + 1,), jnp.bool_).at[slot].set(
        keep, mode="drop")[:-1]
    buf = x_row[slot_tok] * slot_valid[:, None].astype(x_row.dtype)
    return buf.reshape(e_count, C, D), (slot_tok, slot_w, slot_valid)


def _combine_row(y_row, meta, *, S: int, D: int):
    """Scatter-add local expert outputs back to tokens: O(e_count*C*D)."""
    slot_tok, slot_w, slot_valid = meta
    contrib = y_row * (slot_w * slot_valid)[:, None].astype(y_row.dtype)
    out = jnp.zeros((S, D), y_row.dtype)
    return out.at[slot_tok].add(contrib, mode="drop")


def _expert_ffn(buffers, wg, wu, wd):
    """(B, E, C, D) x (E, D, F) -> (B, E, C, D), batched rectangular GEMMs."""
    g = jnp.einsum("becd,edf->becf", buffers, wg)
    u = jnp.einsum("becd,edf->becf", buffers, wu)
    return jnp.einsum("becf,efd->becd", jax.nn.silu(g) * u, wd)


def moe(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
        capacity_factor: float = 1.25) -> Tuple[jax.Array, jax.Array]:
    """Reference path (no mesh): x (B, S, D) -> (out, aux_loss)."""
    B, S, D = x.shape
    E = n_experts
    C = _capacity(S, top_k, E, capacity_factor)

    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    w, idx = _route(logits.reshape(B * S, E), top_k)
    w = w.reshape(B, S, top_k)
    idx = idx.reshape(B, S, top_k)
    aux = _aux_loss(logits, idx, E)

    buffers, meta = jax.vmap(
        lambda xr, wr, ir: _dispatch_row(
            xr, wr, ir, n_experts=E, top_k=top_k, C=C, e_first=0, e_count=E)
    )(x, w, idx)                                            # (B, E, C, D)
    ye = _expert_ffn(buffers, p["w_gate"], p["w_up"], p["w_down"])
    ye = ye.reshape(B, E * C, D)
    out = jax.vmap(
        lambda yr, mr: _combine_row(yr, mr, S=S, D=D)
    )(ye, meta)
    return out.astype(x.dtype), aux


def moe_ep(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
           capacity_factor: float, mesh, model_axis: str = "model"
           ) -> Tuple[jax.Array, jax.Array]:
    """Expert-parallel path (production): see module docstring."""
    B, S, D = x.shape
    E = n_experts
    tp = mesh.shape[model_axis]
    e_loc = E // tp
    C = _capacity(S, top_k, E, capacity_factor)
    batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)

    def local_fn(router, wg, wu, wd, x_loc):
        # x_loc (B_loc, S, D) — replicated over model_axis; wg (e_loc, D, F)
        e_first = jax.lax.axis_index(model_axis) * e_loc
        Bl = x_loc.shape[0]
        logits = jnp.einsum("bsd,de->bse", x_loc.astype(jnp.float32), router)
        w, idx = _route(logits.reshape(Bl * S, E), top_k)
        w = w.reshape(Bl, S, top_k)
        idx = idx.reshape(Bl, S, top_k)
        aux = _aux_loss(logits, idx, E)

        buffers, meta = jax.vmap(
            lambda xr, wr, ir: _dispatch_row(
                xr, wr, ir, n_experts=E, top_k=top_k, C=C,
                e_first=e_first, e_count=e_loc)
        )(x_loc, w, idx)                                    # (Bl, e_loc, C, D)
        ye = _expert_ffn(buffers, wg, wu, wd)
        ye = ye.reshape(Bl, e_loc * C, D)
        part = jax.vmap(
            lambda yr, mr: _combine_row(yr, mr, S=S, D=D)
        )(ye, meta)
        # combine expert partial outputs — the EP "all-to-all return trip"
        # collapsed into one all-reduce (same bytes as a TP MLP psum)
        return jax.lax.psum(part, model_axis), aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(model_axis), P(model_axis), P(model_axis),
                  P(batch_axes)),
        out_specs=(P(batch_axes), P()),
        check_vma=False)
    out, aux = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return out.astype(x.dtype), aux


def moe_ep_a2a(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
               capacity_factor: float, mesh, model_axis: str = "model"
               ) -> Tuple[jax.Array, jax.Array]:
    """All-to-all expert parallelism (hillclimb H1-iter3; EXPERIMENTS §Perf).

    moe_ep() gathers the full sequence onto every device and psums the
    output back — 2 full-activation collectives per layer.  Here the
    sequence stays sharded over `model_axis`: each device routes only its
    S/tp token slice into capacity-bounded per-destination buffers, ONE
    all-to-all ships tokens to their expert owners, expert FFNs run, and a
    second all-to-all returns outputs to be combined locally.  Wire bytes
    drop from ~2*S*D to ~2*(S/tp)*k*cf*D per device — the GShard/Switch
    pattern expressed TPU-natively.
    """
    B, S, D = x.shape
    E = n_experts
    tp = mesh.shape[model_axis]
    e_loc = E // tp
    S_loc = S // tp
    C = _capacity(S_loc, top_k, E, capacity_factor)   # per (src, expert)
    batch_axes = tuple(a for a in mesh.axis_names if a != model_axis)

    def local_fn(router, wg, wu, wd, x_loc):
        # x_loc (Bl, S_loc, D); wg (e_loc, D, F)
        Bl = x_loc.shape[0]
        logits = jnp.einsum("bsd,de->bse", x_loc.astype(jnp.float32), router)
        w, idx = _route(logits.reshape(Bl * S_loc, E), top_k)
        w = w.reshape(Bl, S_loc, top_k)
        idx = idx.reshape(Bl, S_loc, top_k)
        aux = jax.lax.pmean(_aux_loss(logits, idx, E), model_axis)

        # local dispatch into per-(destination expert) buffers
        buffers, meta = jax.vmap(
            lambda xr, wr, ir: _dispatch_row(
                xr, wr, ir, n_experts=E, top_k=top_k, C=C,
                e_first=0, e_count=E)
        )(x_loc, w, idx)                               # (Bl, E, C, D)

        # ship to owners: (E = tp*e_loc) -> exchange over the leading tp
        send = buffers.reshape(Bl, tp, e_loc, C, D).transpose(1, 0, 2, 3, 4)
        recv = jax.lax.all_to_all(send, model_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv (tp=src, Bl, e_loc, C, D): all slots this device's experts own
        xe = recv.transpose(1, 2, 0, 3, 4).reshape(Bl, e_loc, tp * C, D)
        ye = _expert_ffn(xe, wg, wu, wd)
        back = ye.reshape(Bl, e_loc, tp, C, D).transpose(2, 0, 1, 3, 4)
        ret = jax.lax.all_to_all(back, model_axis, split_axis=0,
                                 concat_axis=0, tiled=False)
        # ret (tp=dest-expert-group, Bl, e_loc, C, D) == original slot layout
        y = ret.transpose(1, 0, 2, 3, 4).reshape(Bl, E * C, D)
        out = jax.vmap(
            lambda yr, mr: _combine_row(yr, mr, S=S_loc, D=D)
        )(y, meta)
        return out, aux

    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(), P(model_axis), P(model_axis), P(model_axis),
                  P(batch_axes, model_axis)),
        out_specs=(P(batch_axes, model_axis), P()),
        check_vma=False)
    out, aux = fn(p["router"], p["w_gate"], p["w_up"], p["w_down"], x)
    return out.astype(x.dtype), aux


def moe_decode(p: Params, x: jax.Array, *, n_experts: int, top_k: int
               ) -> jax.Array:
    """Decode path (S small): dense over experts + sparse combine."""
    B, S, D = x.shape
    E, k = n_experts, top_k
    T = B * S
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    w, idx = _route(logits.reshape(T, E), k)                # (T, k)
    xt = x.reshape(T, D)
    g = jnp.einsum("td,edf->etf", xt, p["w_gate"])
    u = jnp.einsum("td,edf->etf", xt, p["w_up"])
    y = jnp.einsum("etf,efd->etd", jax.nn.silu(g) * u, p["w_down"])
    we = jnp.zeros((T, E), jnp.float32).at[
        jnp.arange(T)[:, None], idx].add(w)                 # sparse combine
    out = jnp.einsum("etd,te->td", y.astype(jnp.float32), we)
    return out.reshape(B, S, D).astype(x.dtype)


def moe_dropless(p: Params, x: jax.Array, *, n_experts: int, top_k: int,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """Served path: x (B, S, D) -> (out, rows (E,) int32), ``rows[e]`` the
    token copies routed to expert e.  Routing is f32 softmax over all E
    experts, top-k, renormalised; no token is dropped.

    With ``layer``, the expert weights are every layer's, stacked
    (layers, E, ...), and this layer's are read where they lie: the stack
    is one group list of layers x E groups, all empty but this layer's
    (a slice of the stack would be copied for each grouped GEMM)."""
    B, S, D = x.shape
    T, k = B * S, top_k
    xt = x.reshape(T, D)
    logits = jnp.dot(xt.astype(jnp.float32), p["router"])
    w, idx = _route(logits, k)                              # (T, k)
    flat = idx.reshape(T * k)
    order = jnp.argsort(flat, stable=True)                  # copies by expert
    rows = jnp.bincount(flat, length=n_experts).astype(jnp.int32)
    wg, wu, wd = p["w_gate"], p["w_up"], p["w_down"]
    groups = rows
    if layer is not None:
        wg, wu, wd = (t.reshape(-1, *t.shape[2:]) for t in (wg, wu, wd))
        groups = jax.lax.dynamic_update_slice(
            jnp.zeros((wg.shape[0],), jnp.int32), rows, (layer * n_experts,))
    xs = xt[order // k]                                     # (T*k, D)
    g = dispatch.grouped_matmul(xs, wg, groups)
    u = dispatch.grouped_matmul(xs, wu, groups)
    y = dispatch.grouped_matmul(jax.nn.silu(g) * u, wd, groups)
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))                 # inverse of order
    y = y[back].reshape(T, k, D).astype(jnp.float32)
    out = jnp.einsum("tkd,tk->td", y, w)
    return out.reshape(B, S, D).astype(x.dtype), rows
