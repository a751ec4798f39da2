"""Kernel dispatch: route model compute through tuned kernels.

On TPU, ``matmul``/``conv2d``/... run the compiled Pallas kernels with the
input-aware configuration from the installed tuner (the paper's §6 runtime:
input parameters fixed by the call site, tuning parameters inferred and
cached).  On CPU — including the multi-pod dry-run — they lower to plain XLA
ops so ``cost_analysis()`` reflects the true dataflow (DESIGN.md §4), unless
``prefer_kernel`` asks for the Pallas path, which then runs interpreted.

``check_config`` runs a Pallas kernel (compiled on a TPU, interpreted
elsewhere) against its ref.py oracle — the correctness notion of kernel
legality used by InterpretBackend and the test suite.
"""

from __future__ import annotations

import warnings
from typing import Dict, Mapping, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.space import gemm_default_config, gmm_default_tiles

from . import ops, ref


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


# one warning per (reason, space): a degraded serving process says so once,
# then keeps serving on the heuristic tier instead of spamming or crashing.
# The latch only throttles the *log line* — every occurrence still counts
# in the ``tunedb_dispatch_degraded_calls_total{reason,space}`` counter, so
# a process quietly living on vendor heuristics is visible in /metrics
# even though it warned exactly once.
_WARNED: set = set()
_DEGRADED_COUNTER = None        # bound lazily: obs must not import at startup


def _count_degraded(reason: str, space: str) -> None:
    global _DEGRADED_COUNTER
    counter = _DEGRADED_COUNTER
    if counter is None:
        try:
            from repro.tunedb.obs.metrics import get_registry
        except Exception:       # obs unavailable: degrade silently
            return
        counter = _DEGRADED_COUNTER = lambda r, s: get_registry().counter(
            "tunedb_dispatch_degraded_calls_total",
            "dispatches served by the heuristic fallback tier",
        ).inc(reason=r, space=s)
    try:
        counter(reason, space)
    except Exception:           # observability must never block dispatch
        pass


def _warn_once(key: tuple, msg: str) -> None:
    _count_degraded(str(key[0]), str(key[1]) if len(key) > 1 else "")
    if key not in _WARNED:
        _WARNED.add(key)
        warnings.warn(msg, RuntimeWarning, stacklevel=3)


def reset_fallback_warnings() -> None:
    """Re-arm the warn-once latches (tests; store/model reinstall).

    ``tunedb.store.install_serving`` calls this on EVERY install/hot-swap:
    a fresh store or ModelSet generation that degrades deserves its own
    warning — a latch left over from a degraded predecessor must not
    silently swallow it.
    """
    _WARNED.clear()


_HEURISTIC_LIBS: Dict[str, object] = {}


def _heuristic_cfg(space_name: str, inputs: Mapping[str, int]
                   ) -> Optional[Dict[str, int]]:
    """Last-resort config: the vendor-style size-bucket heuristics.

    Serving keeps running — slower, never wrong — when every tuned tier
    (store record, model, nearest neighbor) comes up empty.
    """
    if space_name not in ("gemm", "conv"):
        return None                     # ops-layer defaults cover attn/ssd
    lib = _HEURISTIC_LIBS.get(space_name)
    if lib is None:
        from repro.core.heuristics import VendorHeuristicLibrary
        from repro.core.space import SPACES
        maker = (VendorHeuristicLibrary.gemm if space_name == "gemm"
                 else VendorHeuristicLibrary.conv)
        lib = _HEURISTIC_LIBS[space_name] = maker(SPACES[space_name])
    return dict(lib.select(inputs))


# lazily bound tuner/serving-state accessors (_tuned_cfg); import-time
# binding would cycle through repro.tunedb.store -> this module
_GET_TUNER = None
_SERVING_STATE = None
# the trace module, bound on first resolution (False = unavailable).  The
# per-call tracing probe is ONE module-attribute read (`_TRACE._TRACER`):
# with tracing disabled that attribute is None and the resolution path is
# byte-identical to the untraced one — the E18 zero-instrument-call gate.
_TRACE = None


def _dtype_bits(dtype) -> int:
    """Bit width of a dtype; safe on integer inputs (jnp.finfo floats only)."""
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.finfo(dtype).bits
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).bits
    return 32


def _tuned_cfg(space_name: str, inputs: Mapping[str, int]
               ) -> Optional[Dict[str, int]]:
    """Config resolution for a serving process with no tuner.

    Tier 0 is the **frozen dispatch plan** (PR 5): ``install_serving``
    compiles the generation's (store, ModelSet, telemetry hot set) into one
    flat shape->config table, so the steady-state hot set resolves with a
    single lock-free dict probe — no sha1 key digest, no model scan, no
    neighbor search.  The plan stands aside (``store.version`` moved past
    the version it was compiled from) the moment the store gains a record,
    so a frozen entry never shadows a fresher tuning outcome.

    Plan misses fall into the PR 2 three-tier slow path:

      1. exact record hit   — the store's fingerprint-keyed index;
      2. model-guided       — the per-(space, backend) performance regressor
                              scores every legal config in one batched MLP
                              forward pass (paper §6) and its pick is
                              memoized per shape;
      3. nearest neighbor   — the closest tuned shape's config, the pre-model
                              fallback, now only for shapes the model tier
                              cannot serve (no trained model, no legal cfg).

    A successful slow-path resolution is PROMOTED into the plan's overlay,
    so every shape pays the full stack at most once per generation.  An
    installed tuner (training/benchmark processes) short-circuits all of
    it.  If every tier misses but tuned serving was *configured* (a store or
    models are installed), dispatch degrades to the vendor-style heuristics
    and warns once — a missing/torn store file or an unreadable model
    artifact must never take serving down.

    The store, ModelSet, fingerprint pin, and plan come from ONE atomic
    ``serving_state()`` read: a concurrent retune hot-swap
    (``install_serving``) flips the whole generation at once, so a
    resolution never mixes the old store with the new models (or an old
    plan with a new store) — the plan a reader holds always belongs to the
    generation it read.
    """
    global _TRACE
    t = _TRACE
    if t is None:
        try:
            from repro.tunedb.obs import trace as t
        except Exception:
            t = False
        _TRACE = t
    tr = t._TRACER if t else None
    if tr is not None:
        # tracing enabled: time the resolution under the thread's current
        # trace (a no-op context when this thread has no sampled trace
        # open), attributing the winning tier and shape key
        with tr.span("dispatch.resolve", space=space_name) as sp:
            cfg, tier = _resolve_cfg(space_name, inputs)
            if sp is not None:
                sp.attrs["tier"] = tier
                sp.attrs["shape"] = ",".join(
                    f"{k}={v}" for k, v in sorted(inputs.items()))
                if space_name == "gemm":
                    # the blocks the call gets: an untuned process's are
                    # the shape rule's (what ops.matmul picks for None)
                    used = cfg or gemm_default_config(
                        inputs["M"], inputs["N"], inputs["K"],
                        inputs["dtype_bits"])
                    sp.attrs["blocks"] = ",".join(
                        str(used[k]) for k in ("bm", "bn", "bk"))
        return cfg
    return _resolve_cfg(space_name, inputs)[0]


def _resolve_cfg(space_name: str, inputs: Mapping[str, int]
                 ) -> tuple:
    """The tier-resolution body of :func:`_tuned_cfg`, returning
    ``(config, winning tier)`` — tier is one of ``tuner``/``none``/
    ``plan``/``exact``/``model``/``nearest``/``degraded``."""
    global _GET_TUNER, _SERVING_STATE
    if _GET_TUNER is None:
        # bound once: the per-call `from x import y` module-dict round
        # trips are measurable against the single-probe plan path
        from repro.core.tuner import get_tuner
        from repro.tunedb.store import serving_state
        _GET_TUNER, _SERVING_STATE = get_tuner, serving_state
    tuner = _GET_TUNER(space_name)
    if tuner is not None:
        return tuner.best_config(inputs, remeasure=False), "tuner"
    state = _SERVING_STATE()
    store, models, fp = state.store, state.models, state.fingerprint
    plan = state.plan
    if store is None and models is None and plan is None:
        return None, "none"              # untuned process: ops.matmul picks
                                         # blocks from the shape
    key = None
    if plan is not None and (store is None
                             or store.version == plan.store_version):
        key = tuple(sorted(inputs.items()))      # store.shape_key, inlined
        entry = plan.lookup(space_name, key)
        if entry is not None:            # tier 0: frozen plan hit
            cfg, tier = entry
            plan.hits += 1
            # plan hits keep the per-tier serving statistics honest: the
            # entry's originating tier gets the credit it would have
            # earned on the slow path — including the exact-tier MISS a
            # model/nearest-served shape books there (store coverage must
            # not inflate just because the plan warmed up)
            if store is None:            # plan-only serving (golden artifact
                pass                     # cold start): no store to credit
            elif tier == "exact":
                store.hits += 1
            elif tier == "nearest":
                store.misses += 1
                store.nearest_hits += 1
            else:
                if store is not None:
                    store.misses += 1
                if models is not None:   # duck-typed stubs may lack counters
                    models.hits = getattr(models, "hits", 0) + 1
            return dict(cfg), "plan"
        plan.misses += 1
    cfg = tier = None
    if store is not None:
        rec = store.get(space_name, inputs, backend=fp)
        if rec is not None:              # tier 1: exact record hit
            cfg, tier = rec.config, "exact"
    if cfg is None and models is not None:
        got = models.predict(space_name, inputs, backend=fp)
        if got is not None:              # tier 2: model-guided search
            cfg, tier = got[0], "model"
    if cfg is None and store is not None:
        rec = store.nearest(space_name, inputs, backend=fp)
        if rec is not None:              # tier 3: nearest tuned neighbor
            cfg, tier = rec.config, "nearest"
    if cfg is not None:
        if key is not None and (store is None
                                or store.version == plan.store_version):
            plan.promote(space_name, key, cfg, tier)
        return dict(cfg), tier
    _warn_once(("untuned", space_name),
               f"tunedb: no record, model, or neighbor for a {space_name} "
               f"shape {dict(inputs)}; serving on vendor heuristics")
    return _heuristic_cfg(space_name, inputs), "degraded"


def _record(space_name: str, inputs: Mapping[str, int]) -> None:
    from repro.tunedb.telemetry import record_shape
    record_shape(space_name, inputs)


def matmul(a: jax.Array, b: jax.Array, *, prefer_kernel: bool = False
           ) -> jax.Array:
    """Model-facing GEMM.  prefer_kernel forces the Pallas path (tests)."""
    if a.ndim == 2 and b.ndim == 2:     # non-2D operands: plain jnp.dot only
        from repro.core.space import gemm_input
        inputs = gemm_input(a.shape[0], b.shape[1], a.shape[1],
                            _dtype_bits(a.dtype))
        _record("gemm", inputs)
        if on_tpu() or prefer_kernel:
            cfg = _tuned_cfg("gemm", inputs)
            return ops.matmul(a, b, cfg)
    return jnp.dot(a, b)


def matmul2(x: jax.Array, w: jax.Array, *, prefer_kernel: bool = False
            ) -> jax.Array:
    """Projection GEMM (..., D) @ (D, F) -> (..., F): the model-facing entry
    point.  Leading dims fold into M, so the tuner sees the true GEMM shape."""
    lead = x.shape[:-1]
    if on_tpu() or prefer_kernel:
        x2 = x.reshape(-1, x.shape[-1])
        return matmul(x2, w, prefer_kernel=prefer_kernel).reshape(*lead,
                                                                  w.shape[-1])
    from repro.core.space import gemm_input
    M = 1
    for d in lead:
        M *= d
    _record("gemm", gemm_input(M, w.shape[-1], x.shape[-1],
                               _dtype_bits(x.dtype)))
    return jnp.dot(x, w)


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   *, prefer_kernel: bool = False) -> jax.Array:
    """Grouped expert GEMM: ``lhs`` (M, K) holds the rows of each group in
    turn, ``group_sizes`` (G,) how many each has (known only at run time),
    and group g's rows are multiplied by ``rhs[g]`` (K, N) -> (M, N).

    On a TPU (or with ``prefer_kernel``) the Pallas grouped GEMM runs with
    tiles from the shape (``gmm_default_tiles``); where the rule names no
    tiles, and off a TPU, XLA's ragged dot runs."""
    M, K = lhs.shape
    N = rhs.shape[2]
    tiles = None
    if on_tpu() or prefer_kernel:
        tiles = gmm_default_tiles(M, N, K, _dtype_bits(lhs.dtype))
    if tiles is None:
        return jax.lax.ragged_dot(lhs, rhs, group_sizes)
    return ops.grouped_matmul(lhs, rhs, group_sizes, tiles)


def conv2d(i: jax.Array, f: jax.Array, *, prefer_kernel: bool = False
           ) -> jax.Array:
    from repro.core.space import conv_input
    N, H, W, C = i.shape
    R, S, _, K = f.shape
    inputs = conv_input(N, H, W, C, K, R, S, _dtype_bits(i.dtype))
    _record("conv", inputs)
    if on_tpu() or prefer_kernel:
        cfg = _tuned_cfg("conv", inputs)
        return ops.conv2d(i, f, cfg)
    return ref.conv2d_ref(i, f)


def flash_attention(q, k, v, *, causal=True, q_offset=0,
                    prefer_kernel: bool = False):
    inputs = {"B": q.shape[0], "Hq": q.shape[1], "Hkv": k.shape[1],
              "Lq": q.shape[2], "Lkv": k.shape[2], "D": q.shape[3],
              "dtype_bits": _dtype_bits(q.dtype), "causal": int(causal)}
    _record("attention", inputs)
    if on_tpu() or prefer_kernel:
        cfg = _tuned_cfg("attention", inputs)
        return ops.flash_attention(q, k, v, cfg, causal=causal,
                                   q_offset=q_offset)
    return ref.attention_ref(q, k, v, causal=causal, q_offset=q_offset)


def ssd_scan(x, dt, a, bm, cm, *, prefer_kernel: bool = False):
    inputs = {"B": x.shape[0], "L": x.shape[1], "H": x.shape[2],
              "P": x.shape[3], "S": bm.shape[-1],
              "dtype_bits": _dtype_bits(x.dtype)}
    _record("ssd", inputs)
    if on_tpu() or prefer_kernel:
        cfg = _tuned_cfg("ssd", inputs)
        return ops.ssd_scan(x, dt, a, bm, cm, cfg)
    # CPU/dry-run path: chunked-but-pure-jnp SSD (identical math, XLA ops)
    return ref.ssd_ref(x, dt, a, bm, cm)


# ---------------------------------------------------------------------------
# Correctness gate used by InterpretBackend + tests
# ---------------------------------------------------------------------------

def check_config(space_name: str, cfg: Dict[str, int],
                 inputs: Dict[str, int], *, rtol: float = 2e-2,
                 seed: int = 0, max_dim: int = 512) -> None:
    """Run the Pallas kernel for `cfg` on a shrunken instance of `inputs`
    (compiled on a TPU, interpreted elsewhere) and assert allclose against
    the jnp oracle.  Raises on mismatch.  Dims are capped at max_dim to keep
    interpret mode fast — the config's *structure* (splits, unrolls, block
    shapes) is exercised fully.
    """
    rng = np.random.default_rng(seed)
    dtype = jnp.bfloat16 if inputs.get("dtype_bits", 16) <= 16 else jnp.float32
    cap = lambda v: int(min(v, max_dim))

    if space_name == "gemm":
        M, N, K = cap(inputs["M"]), cap(inputs["N"]), cap(inputs["K"])
        a = jnp.asarray(rng.normal(size=(M, K)), dtype)
        b = jnp.asarray(rng.normal(size=(K, N)), dtype)
        got = ops.matmul(a, b, cfg)
        want = ref.matmul_ref(a, b)
    elif space_name == "conv":
        N, H, W = cap(inputs["N"]), cap(inputs["H"]), cap(inputs["W"])
        C, K = cap(inputs["C"]), cap(inputs["K"])
        R, S = inputs["R"], inputs["S"]
        i = jnp.asarray(rng.normal(size=(min(N, 2), min(H, 16), min(W, 16), C)),
                        dtype)
        f = jnp.asarray(rng.normal(size=(R, S, C, K)) / (R * S * C) ** 0.5,
                        dtype)
        got = ops.conv2d(i, f, cfg)
        want = ref.conv2d_ref(i, f)
    elif space_name == "attention":
        B, Hq, Hkv = min(inputs["B"], 2), min(inputs["Hq"], 4), inputs["Hkv"]
        Hkv = min(Hkv, Hq)
        while Hq % Hkv:
            Hkv -= 1
        Lq, Lkv, D = cap(inputs["Lq"]), cap(inputs["Lkv"]), min(inputs["D"], 128)
        causal = bool(inputs.get("causal", 1)) and Lq == Lkv
        q = jnp.asarray(rng.normal(size=(B, Hq, Lq, D)), dtype)
        k = jnp.asarray(rng.normal(size=(B, Hkv, Lkv, D)), dtype)
        v = jnp.asarray(rng.normal(size=(B, Hkv, Lkv, D)), dtype)
        got = ops.flash_attention(q, k, v, cfg, causal=causal)
        want = ref.attention_ref(q, k, v, causal=causal)
    elif space_name == "ssd":
        B, L = min(inputs["B"], 2), cap(inputs["L"])
        H, P, S = min(inputs["H"], 4), min(inputs["P"], 64), min(inputs["S"], 64)
        x = jnp.asarray(rng.normal(size=(B, L, H, P)), dtype)
        dt = jnp.asarray(rng.uniform(0.01, 0.1, size=(B, L, H)), dtype)
        a = -jnp.asarray(rng.uniform(0.5, 2.0, size=(H,)), jnp.float32)
        bm = jnp.asarray(rng.normal(size=(B, L, S)), dtype)
        cm = jnp.asarray(rng.normal(size=(B, L, S)), dtype)
        got = ops.ssd_scan(x, dt, a, bm, cm, cfg)
        want = ref.ssd_ref(x, dt, a, bm, cm)
    else:
        raise ValueError(space_name)

    g = np.asarray(got, np.float32)
    w = np.asarray(want, np.float32)
    scale = max(float(np.abs(w).max()), 1e-6)
    err = float(np.abs(g - w).max()) / scale
    if not np.isfinite(g).all():
        raise AssertionError(f"{space_name} cfg {cfg}: non-finite output")
    if err > rtol:
        raise AssertionError(
            f"{space_name} cfg {cfg}: rel err {err:.4f} > {rtol}")
