"""Unified composable LM: dense / MoE / SSM / hybrid / enc-dec / stub-frontend.

One :class:`ModelConfig` covers all ten assigned architectures.  Layers are
described by a repeating *pattern* of (mixer, mlp) pairs; parameters for each
pattern position are stacked over the repeat count and the forward pass is a
``jax.lax.scan`` over repeats (essential for the 126-layer llama3-405b HLO to
stay compact) with optional remat.

Entry points:
  init_params(cfg, key)                     -> param pytree
  forward(params, cfg, batch)               -> logits/hidden (training path)
  loss_fn(params, cfg, batch)               -> (loss, metrics)
  init_cache(cfg, batch, max_len)           -> decode cache pytree
  prefill(params, cfg, batch, cache)        -> (last_logits, cache)
  decode_step(params, cfg, tokens, cache, index) -> (logits, cache)
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.parallel.sharding import constrain
from . import layers as L
from . import moe as MOE
from . import ssm as SSM

Params = Dict[str, Any]

ATTN, ATTN_WINDOW, MAMBA = "attn", "attn_window", "mamba"
ATTN_KINDS = (ATTN, ATTN_WINDOW)     # full causal, sliding-window causal
DENSE, MOE_MLP, MOE_DENSE, NONE = "dense", "moe", "moe+dense", "none"


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv: int
    d_ff: int
    vocab: int
    head_dim: int = 0                      # 0 => d_model // n_heads
    # pattern: ((mixer, mlp), ...) repeated n_layers // len(pattern) times
    pattern: Tuple[Tuple[str, str], ...] = ((ATTN, DENSE),)
    # sliding-window layers (mixer ATTN_WINDOW) see this many positions back
    sliding_window: int = 0
    # MoE
    n_experts: int = 0
    top_k: int = 0
    capacity_factor: float = 1.25
    # SSM
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssd_chunk: int = 256
    # encoder-decoder (whisper): encoder_layers > 0 enables cross-attention
    encoder_layers: int = 0
    encoder_len: int = 0                   # stub frame count
    # stub frontends: 'none' | 'audio' | 'vision'
    frontend: str = "none"
    n_frontend_tokens: int = 0             # vision: patch embeds prepended
    # numerics / structure
    qk_norm: bool = False
    rope_theta: float = 1e4
    rope_yarn: Optional[L.YaRN] = None     # YaRN scaling of full layers' RoPE
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    remat: bool = True
    attn_chunk: int = 1024
    logit_chunk: int = 512
    tie_embeddings: bool = True
    decode_kv_splits: int = 1      # >1: SP flash-decoding over the KV cache
    # --- beyond-paper perf knobs (EXPERIMENTS.md §Perf; default = baseline)
    causal_block_skip: bool = False   # skip upper-tri attention blocks (~2x)
    decode_replicate_acts: bool = False  # decode: replicate tiny activations
    #   so projections consume 2D-TP weights in place (no weight gathers)
    moe_a2a: bool = False             # all-to-all EP (vs gather+psum EP)
    mlp_tp: bool = True               # False: pure-SP MLP (tiny models whose
    #   TP slices are smaller than the resharding they cost; pair w/ dp_only)
    # cost-accounting mode (launch/dryrun.py): XLA cost_analysis counts a
    # while-loop body ONCE, so for exact FLOP/byte/collective accounting the
    # dry-run compiles reduced-depth configs with every scan unrolled and
    # extrapolates linearly in depth.  Never set for real execution.
    unroll_scan: bool = False

    # ---- derived -----------------------------------------------------------
    @property
    def hd(self) -> int:
        return self.head_dim or (self.d_model // max(self.n_heads, 1))

    @property
    def padded_vocab(self) -> int:
        return _round_up(self.vocab, 256)

    @property
    def n_repeats(self) -> int:
        assert self.n_layers % len(self.pattern) == 0, (
            self.name, self.n_layers, len(self.pattern))
        return self.n_layers // len(self.pattern)

    @property
    def is_encdec(self) -> bool:
        return self.encoder_layers > 0

    @property
    def param_count(self) -> int:
        """Total parameters (for 6ND model-FLOPs accounting)."""
        return self._count_params(active=False)

    @property
    def active_param_count(self) -> int:
        """Active parameters per token (MoE: only top_k experts count)."""
        return self._count_params(active=True)

    def _count_params(self, active: bool) -> int:
        n = self.padded_vocab * self.d_model      # embed (tied head)
        if not self.tie_embeddings:
            n *= 2
        n += self.d_model                         # final norm
        per = self._layer_params(active=active)
        n += self.n_repeats * sum(per)
        if self.is_encdec:
            # decoder cross-attention blocks (+ their norms)
            n += self.n_layers * (self._attn_params() + self.d_model)
            # encoder stack: plain (attn, dense) layers + final norm
            enc = (self._attn_params() + 3 * self.d_model * self.d_ff
                   + 2 * self.d_model)
            n += self.encoder_layers * enc + self.d_model
        return n

    def _attn_params(self) -> int:
        d, hd = self.d_model, self.hd
        return d * (self.n_heads + 2 * self.n_kv) * hd + self.n_heads * hd * d

    def _layer_params(self, active: bool = False) -> Tuple[int, ...]:
        d, f = self.d_model, self.d_ff
        out = []
        for mixer, mlp_kind in self.pattern:
            n = 2 * d                                        # norms
            if mixer in ATTN_KINDS:
                n += self._attn_params()
            else:
                di = 2 * d
                nh = di // self.ssm_head_dim
                n += d * (2 * di + 2 * self.ssm_state + nh) + di * d
            if mlp_kind in (DENSE, MOE_DENSE):
                n += 3 * d * f
            if mlp_kind in (MOE_MLP, MOE_DENSE):
                e = self.top_k if active else self.n_experts
                n += d * self.n_experts + e * 3 * d * f
            out.append(n)
        return tuple(out)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(cfg: ModelConfig, key: jax.Array, mixer: str, mlp_kind: str,
                cross: bool) -> Params:
    ks = jax.random.split(key, 6)
    p: Params = {"norm1": jnp.ones((cfg.d_model,), cfg.dtype),
                 "norm2": jnp.ones((cfg.d_model,), cfg.dtype)}
    if mixer in ATTN_KINDS:
        p["attn"] = L.init_attention(ks[0], cfg.d_model, cfg.n_heads,
                                     cfg.n_kv, cfg.hd, cfg.qk_norm, cfg.dtype)
    else:
        p["mamba"] = SSM.init_mamba(ks[0], cfg.d_model, cfg.ssm_state,
                                    cfg.ssm_head_dim, cfg.dtype)
    if cross:
        p["cross"] = L.init_attention(ks[1], cfg.d_model, cfg.n_heads,
                                      cfg.n_kv, cfg.hd, False, cfg.dtype)
        p["norm_cross"] = jnp.ones((cfg.d_model,), cfg.dtype)
    if mlp_kind in (DENSE, MOE_DENSE):
        p["mlp"] = L.init_mlp(ks[2], cfg.d_model, cfg.d_ff, cfg.dtype)
    if mlp_kind in (MOE_MLP, MOE_DENSE):
        p["moe"] = MOE.init_moe(ks[3], cfg.d_model, cfg.d_ff, cfg.n_experts,
                                cfg.dtype)
    return p


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    keys = jax.random.split(key, 4 + len(cfg.pattern))
    params: Params = {
        "embed": L.embed_init(keys[0], cfg.padded_vocab, cfg.d_model,
                              cfg.dtype),
        "final_norm": jnp.ones((cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = L.embed_init(keys[-2], cfg.padded_vocab,
                                         cfg.d_model, cfg.dtype)
    # decoder stack: per pattern position, stacked over repeats
    layer_tree: Params = {}
    for i, (mixer, mlp_kind) in enumerate(cfg.pattern):
        def one(k):
            return _init_layer(cfg, k, mixer, mlp_kind, cross=cfg.is_encdec)
        ks = jax.random.split(keys[1 + i], cfg.n_repeats)
        layer_tree[f"pos{i}"] = jax.vmap(one)(ks)
    params["layers"] = layer_tree

    if cfg.is_encdec:
        def enc_one(k):
            return _init_layer(cfg, k, ATTN, DENSE, cross=False)
        ks = jax.random.split(keys[-1], cfg.encoder_layers)
        params["encoder"] = {"pos0": jax.vmap(enc_one)(ks),
                             "norm": jnp.ones((cfg.d_model,), cfg.dtype)}
    return params


# ---------------------------------------------------------------------------
# layer application
# ---------------------------------------------------------------------------

def _apply_layer(cfg: ModelConfig, p: Params, x: jax.Array, *,
                 mixer: str, mlp_kind: str, positions: jax.Array,
                 causal: bool, memory: Optional[jax.Array],
                 cache: Optional[Params], cache_index,
                 expert_layer: Optional[jax.Array] = None,
                 ) -> Tuple[jax.Array, Optional[Params], jax.Array,
                            Optional[jax.Array]]:
    """Pre-norm residual block.  Returns (x, new_cache, aux_loss, rows):
    rows (E,) int32 are the token copies routed to each expert where the
    served MoE path ran, else None.  ``expert_layer``: the served path's
    expert weights are the whole stack, and this is the layer's index in
    it (``moe_dropless``)."""
    aux = jnp.zeros((), jnp.float32)
    rows = None
    new_cache: Optional[Params] = dict(cache) if cache is not None else None

    if cache is not None and cfg.decode_replicate_acts:
        # decode activations are tiny (B, 1, D).  Sharding their FEATURE dim
        # over 'data' aligns x with the FSDP shard of every weight's
        # contraction dim, so projections lower to partial-matmul + psum of
        # activation-sized tensors instead of gathering weight shards
        # (GSPMD's cost model otherwise picks the 0.5 GiB/layer W-gather
        # over the 0.5 MB psum).  Batch stays replicated across 'data' here;
        # attention re-shards q against the batch-sharded KV cache.
        x = constrain(x, "none", "none", "fsdp")

    def _decode_fsdp(t: jax.Array) -> jax.Array:
        # the norm's cross-D mean breaks feature sharding; re-pin the norm
        # OUTPUT (the projection input) so x @ W contracts against the FSDP
        # weight shard in place instead of gathering W (decode only)
        if cache is not None and cfg.decode_replicate_acts:
            return constrain(t, "none", "none", "fsdp")
        return t

    h = _decode_fsdp(L.rms_norm(x, p["norm1"], cfg.norm_eps))
    if mixer in ATTN_KINDS:
        attn_cache = cache.get("attn") if cache is not None else None
        windowed = mixer == ATTN_WINDOW
        out, nc = L.attention(
            p["attn"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
            positions=positions, causal=causal, rope_theta=cfg.rope_theta,
            qk_norm=cfg.qk_norm, norm_eps=cfg.norm_eps, cache=attn_cache,
            cache_index=cache_index, attn_chunk=cfg.attn_chunk,
            decode_kv_splits=cfg.decode_kv_splits, unroll=cfg.unroll_scan,
            causal_block_skip=cfg.causal_block_skip,
            window=cfg.sliding_window if windowed else 0,
            yarn=None if windowed else cfg.rope_yarn)
        if new_cache is not None:
            new_cache["attn"] = nc
    else:
        mamba_cache = cache.get("mamba") if cache is not None else None
        out, nc = SSM.mamba_block(
            p["mamba"], h, d_model=cfg.d_model, state=cfg.ssm_state,
            head_dim=cfg.ssm_head_dim, chunk=cfg.ssd_chunk, cache=mamba_cache,
            unroll=cfg.unroll_scan)
        if new_cache is not None:
            new_cache["mamba"] = nc
    x = constrain(x + out, "batch", "seq", "none")

    if memory is not None and "cross" in p:
        h = _decode_fsdp(L.rms_norm(x, p["norm_cross"], cfg.norm_eps))
        out, _ = L.attention(
            p["cross"], h, n_heads=cfg.n_heads, n_kv=cfg.n_kv, head_dim=cfg.hd,
            positions=positions, causal=False, rope_theta=cfg.rope_theta,
            qk_norm=False, norm_eps=cfg.norm_eps, memory=memory,
            attn_chunk=cfg.attn_chunk, unroll=cfg.unroll_scan)
        x = x + out

    if mlp_kind != NONE:
        h = _decode_fsdp(L.rms_norm(x, p["norm2"], cfg.norm_eps))
        out = jnp.zeros_like(x)
        if mlp_kind in (DENSE, MOE_DENSE):
            out = out + L.mlp(p["mlp"], h, tp=cfg.mlp_tp)
        if mlp_kind in (MOE_MLP, MOE_DENSE):
            from repro.parallel import sharding as shd
            mesh = shd.active_mesh()
            if cache is not None and mesh is None:
                # served, one device: dropless grouped GEMMs
                mo, rows = MOE.moe_dropless(p["moe"], h,
                                            n_experts=cfg.n_experts,
                                            top_k=cfg.top_k,
                                            layer=expert_layer)
            elif cache is not None and h.shape[1] == 1:
                # decode only: the dense-all-experts path is O(T*E) — right
                # for one token per sequence, catastrophic for a 32k prefill
                mo = MOE.moe_decode(p["moe"], h, n_experts=cfg.n_experts,
                                    top_k=cfg.top_k)
            else:
                ep_ok = (mesh is not None and "model" in mesh.axis_names
                         and cfg.n_experts % mesh.shape["model"] == 0)
                if ep_ok:
                    bsz = 1
                    for a in mesh.axis_names:
                        if a != "model":
                            bsz *= mesh.shape[a]
                    ep_ok = h.shape[0] % bsz == 0
                if ep_ok and cfg.moe_a2a \
                        and h.shape[1] % mesh.shape["model"] == 0:
                    mo, aux = MOE.moe_ep_a2a(
                        p["moe"], h, n_experts=cfg.n_experts,
                        top_k=cfg.top_k,
                        capacity_factor=cfg.capacity_factor, mesh=mesh)
                elif ep_ok:
                    mo, aux = MOE.moe_ep(
                        p["moe"], h, n_experts=cfg.n_experts,
                        top_k=cfg.top_k,
                        capacity_factor=cfg.capacity_factor, mesh=mesh)
                else:
                    mo, aux = MOE.moe(p["moe"], h, n_experts=cfg.n_experts,
                                      top_k=cfg.top_k,
                                      capacity_factor=cfg.capacity_factor)
            out = out + mo
        x = constrain(x + out, "batch", "seq", "none")
    return x, new_cache, aux, rows


def _run_stack(cfg: ModelConfig, stack: Params, x: jax.Array, *,
               pattern: Tuple[Tuple[str, str], ...], positions: jax.Array,
               causal: bool, memory: Optional[jax.Array] = None,
               cache: Optional[Params] = None, cache_index=None,
               ) -> Tuple[jax.Array, Optional[Params], jax.Array,
                          Optional[jax.Array]]:
    """Scan over stacked repeats; pattern positions applied sequentially
    inside the body.  cache (if given) is scanned alongside the params.
    Returns (x, new_cache, aux, rows): rows (layers, E) int32, in layer
    order, where the served MoE path ran, else None."""
    from repro.parallel import sharding as shd
    n_rep = jax.tree_util.tree_leaves(stack)[0].shape[0]
    # the served MoE path (a cache, one device) reads each layer's experts
    # from the whole stack in place: they leave the scanned slices, and the
    # repeat index goes in instead
    experts: Dict[str, Params] = {}
    if cache is not None and shd.active_mesh() is None:
        for i, (_, mlp_kind) in enumerate(pattern):
            if mlp_kind in (MOE_MLP, MOE_DENSE):
                moe = stack[f"pos{i}"]["moe"]
                experts[f"pos{i}"] = {n: moe[n] for n in
                                      ("w_gate", "w_up", "w_down")}
    if experts:
        stack = {pos: ({**lp, "moe": {"router": lp["moe"]["router"]}}
                       if pos in experts else lp)
                 for pos, lp in stack.items()}
        xs = (stack, cache, jnp.arange(n_rep))
    else:
        xs = (stack, cache)

    def body(carry, scanned):
        x, aux = carry
        layer_p, layer_c = scanned[:2]
        new_c: Dict[str, Any] = {}
        rows = []
        for i, (mixer, mlp_kind) in enumerate(pattern):
            c_i = layer_c.get(f"pos{i}") if layer_c is not None else None
            p_i, rep = layer_p[f"pos{i}"], None
            if f"pos{i}" in experts:
                p_i = {**p_i, "moe": {**p_i["moe"], **experts[f"pos{i}"]}}
                rep = scanned[2]
            x, nc, a, r = _apply_layer(
                cfg, p_i,
                x, mixer=mixer, mlp_kind=mlp_kind, positions=positions,
                causal=causal, memory=memory, cache=c_i,
                cache_index=cache_index, expert_layer=rep)
            if nc is not None:
                new_c[f"pos{i}"] = nc
            if r is not None:
                rows.append(r)
            aux = aux + a
        return (x, aux), (new_c if new_c else None,
                          jnp.stack(rows) if rows else None)

    if cfg.remat:
        body = jax.checkpoint(body)

    aux0 = jnp.zeros((), jnp.float32)
    if cfg.unroll_scan:
        # cost-accounting mode: true Python unroll (see ModelConfig)
        carry = (x, aux0)
        ys = []
        for r in range(n_rep):
            sl = jax.tree_util.tree_map(lambda v: v[r], xs)
            carry, y = body(carry, sl)
            ys.append(y)
        (x, aux) = carry
        stacked = jax.tree_util.tree_map(lambda *vs: jnp.stack(vs), *ys)
    else:
        (x, aux), stacked = jax.lax.scan(body, (x, aux0), xs)
    new_cache, rows = stacked
    if rows is not None:                # (repeats, positions, E)
        rows = rows.reshape(-1, rows.shape[-1])
    return x, new_cache, aux, rows


# ---------------------------------------------------------------------------
# embedding / logits
# ---------------------------------------------------------------------------

def _embed(cfg: ModelConfig, params: Params, tokens: jax.Array) -> jax.Array:
    emb = params["embed"][tokens]                 # (B, S, D) gather
    return constrain(emb, "batch", "seq", "none")


def _chunked_xent(cfg: ModelConfig, x: jax.Array, embed: jax.Array,
                  targets: jax.Array, mask: jax.Array
                  ) -> Tuple[jax.Array, jax.Array]:
    """Cross-entropy without materializing full (B, S, V) logits: scan over
    sequence chunks; each chunk's logits live only inside its scan step."""
    B, S, D = x.shape
    ck = min(cfg.logit_chunk, S)
    pad = (-S) % ck
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        mask = jnp.pad(mask, ((0, 0), (0, pad)))
    n = (S + pad) // ck
    xs = x.reshape(B, n, ck, D).transpose(1, 0, 2, 3)
    ts = targets.reshape(B, n, ck).transpose(1, 0, 2)
    ms = mask.reshape(B, n, ck).transpose(1, 0, 2)
    w_t = embed.astype(cfg.dtype)

    def body(carry, inp):
        loss_sum, correct = carry
        xc, tc, mc = inp
        logits = jnp.einsum("bsd,vd->bsv", xc, w_t).astype(jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        loss_sum = loss_sum + jnp.sum((lse - tgt) * mc)
        correct = correct + jnp.sum(
            (jnp.argmax(logits, -1) == tc) * mc)
        return (loss_sum, correct), None

    body = jax.checkpoint(body)
    (loss_sum, correct), _ = jax.lax.scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        (xs, ts, ms), unroll=bool(cfg.unroll_scan))
    denom = jnp.maximum(mask.sum(), 1.0)
    return loss_sum / denom, correct / denom


def _head(cfg: ModelConfig, params: Params) -> jax.Array:
    """The output head's (V, D) table: the embedding's where tied."""
    return params["embed" if cfg.tie_embeddings else "lm_head"]


def _logits(cfg: ModelConfig, params: Params, x: jax.Array) -> jax.Array:
    return jnp.einsum("bsd,vd->bsv", x, _head(cfg, params).astype(cfg.dtype)
                      ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def _frontend_concat(cfg: ModelConfig, params: Params, batch: Dict[str, Any]
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Returns (x (B,S,D), targets (B,S), loss_mask (B,S)) for the decoder."""
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    targets = batch.get("targets", tokens)
    mask = batch.get("loss_mask", jnp.ones(tokens.shape, jnp.float32))
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(cfg.dtype)     # (B, Np, D)
        x = jnp.concatenate([pe, x], axis=1)
        npatch = pe.shape[1]
        targets = jnp.concatenate(
            [jnp.zeros((x.shape[0], npatch), targets.dtype), targets], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((x.shape[0], npatch), mask.dtype), mask], axis=1)
    return x, targets, mask


def encode(cfg: ModelConfig, params: Params, frames: jax.Array) -> jax.Array:
    """Encoder stack over stub frame embeddings (B, L_enc, D)."""
    x = frames.astype(cfg.dtype)
    positions = jnp.arange(x.shape[1])
    x, _, _, _ = _run_stack(cfg, {"pos0": params["encoder"]["pos0"]}, x,
                            pattern=((ATTN, DENSE),), positions=positions,
                            causal=False)
    # encoder params are stored under pos0 stacked; norm applied after
    return L.rms_norm(x, params["encoder"]["norm"], cfg.norm_eps)


def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any]
            ) -> Tuple[jax.Array, jax.Array]:
    """Training/prefill forward.  Returns (final hidden (B,S,D), aux_loss)."""
    x, _, _ = _frontend_concat(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    memory = None
    if cfg.is_encdec:
        memory = encode(cfg, params, batch["encoder_embeds"])
    x, _, aux, _ = _run_stack(cfg, params["layers"], x, pattern=cfg.pattern,
                              positions=positions, causal=True,
                              memory=memory)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            aux_weight: float = 0.01
            ) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    x, aux = forward(params, cfg, batch)
    _, targets, mask = _frontend_concat_shapes(cfg, batch)
    # next-token shift: predict t+1 from t
    x_in = x[:, :-1]
    tgt = targets[:, 1:]
    msk = mask[:, 1:]
    xent, acc = _chunked_xent(cfg, x_in, _head(cfg, params), tgt, msk)
    loss = xent + aux_weight * aux
    return loss, {"loss": loss, "xent": xent, "aux": aux, "acc": acc}


def _frontend_concat_shapes(cfg: ModelConfig, batch: Dict[str, Any]):
    """targets/mask aligned with the (possibly frontend-extended) sequence,
    without re-running the embedding."""
    tokens = batch["tokens"]
    targets = batch.get("targets", tokens)
    mask = batch.get("loss_mask", jnp.ones(tokens.shape, jnp.float32))
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        npatch = batch["patch_embeds"].shape[1]
        B = tokens.shape[0]
        targets = jnp.concatenate(
            [jnp.zeros((B, npatch), targets.dtype), targets], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros((B, npatch), mask.dtype), mask], axis=1)
    return None, targets, mask


# ---------------------------------------------------------------------------
# decode (serving)
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Decode cache pytree, stacked over repeats like the params."""
    cache: Params = {}
    for i, (mixer, _) in enumerate(cfg.pattern):
        if mixer in ATTN_KINDS:
            # a window layer keeps a ring of its window's positions
            length = (min(cfg.sliding_window, max_len)
                      if mixer == ATTN_WINDOW else max_len)
            kv = jnp.zeros((cfg.n_repeats, batch, length, cfg.n_kv, cfg.hd),
                           cfg.dtype)
            cache[f"pos{i}"] = {"attn": {"k": kv, "v": kv}}
        else:
            one = SSM.init_mamba_cache(batch, cfg.d_model, cfg.ssm_state,
                                       cfg.ssm_head_dim, cfg.dtype)
            cache[f"pos{i}"] = {"mamba": jax.tree_util.tree_map(
                lambda v: jnp.broadcast_to(
                    v[None], (cfg.n_repeats,) + v.shape), one)}
    return cache


def decode_step(params: Params, cfg: ModelConfig, tokens: jax.Array,
                cache: Params, index: jax.Array,
                memory: Optional[jax.Array] = None, *,
                expert_rows: bool = False):
    """One decode step.  tokens (B, 1) int32; index = current length —
    scalar, or (B,) for per-slot positions (continuous batching).
    Returns (logits (B, V), new cache), and with ``expert_rows`` the rows
    routed to each expert of each served MoE layer, (layers, E) int32
    (None for a model without one)."""
    x = _embed(cfg, params, tokens)
    positions = (jnp.asarray(index).reshape(-1, 1)
                 + jnp.arange(tokens.shape[1])[None, :])
    if cfg.is_encdec and memory is None:
        raise ValueError("enc-dec decode requires encoder memory")
    x, new_cache, _, rows = _run_stack(
        cfg, params["layers"], x, pattern=cfg.pattern, positions=positions,
        causal=True, memory=memory, cache=cache, cache_index=index)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.decode_replicate_acts:
        # keep the D contraction aligned with the embed table's fsdp shard
        x = constrain(x, "none", "none", "fsdp")
    logits = _logits(cfg, params, x)[:, -1]
    return (logits, new_cache, rows) if expert_rows else (logits, new_cache)


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            cache: Params, *, expert_rows: bool = False):
    """Prefill: run the full prompt through the stack, filling the cache.
    Returns (last-position logits (B, V), cache), and with ``expert_rows``
    the rows routed to each expert as ``decode_step`` gives them."""
    x, _, _ = _frontend_concat(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    memory = None
    if cfg.is_encdec:
        memory = encode(cfg, params, batch["encoder_embeds"])
    x, new_cache, _, rows = _run_stack(
        cfg, params["layers"], x, pattern=cfg.pattern, positions=positions,
        causal=True, memory=memory, cache=cache,
        cache_index=jnp.zeros((), jnp.int32))
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _logits(cfg, params, x[:, -1:])[:, -1]
    return (logits, new_cache, rows) if expert_rows else (logits, new_cache)
