"""E7 — kernel harness: every Pallas kernel validated (interpret mode off
a TPU) against its ref.py oracle on tuner-selected configurations, plus
the compiled GEMM's wall clock when a TPU is attached: the fixed blocks
against the shape rule's pick, its neighbours and ``jnp.dot`` on every
projection shape the benchmark's configurations serve.

    python -m benchmarks.bench_kernels            # the whole harness
    python -m benchmarks.bench_kernels --sweep    # the GEMM sweep alone
    python -m benchmarks.bench_kernels --gmm      # the grouped-GEMM sweep
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.core.backend import InterpretBackend, WallClockBackend
from repro.core.space import (conv_input, gemm_default_config, gemm_input,
                              gemm_is_legal)
from repro.kernels import ops
from repro.kernels.ops import DEFAULT_ATTN, DEFAULT_CONV, DEFAULT_SSD
from .common import get_trained_tuner, save, table

# the blocks every GEMM of an untuned process got before they followed the
# shape: the baseline the shape rule is measured against
FIXED_GEMM = {"bm": 128, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 1,
              "order": 0, "acc32": 1, "prefetch": 2}

# (N, K) of each projection a served configuration runs, and the M its
# cells send: decode slots and prefill lengths
CENSUS = {
    "qwen3-14b": ([(5120, 5120), (1024, 5120), (17408, 5120), (5120, 17408)],
                  [16, 256, 1536, 2560, 3840]),
    "smollm-135m": ([(576, 576), (192, 576), (1536, 576), (576, 1536)],
                    [64, 128, 768, 1920]),
}

# each expert GEMM (N, K) of a sparse-expert configuration, its experts and
# experts per token, and the token counts its cells send: decode slots and
# prefill lengths (the rows are tokens x top_k)
GMM_CENSUS = {
    "mellum2-12b-a2.5b": {"experts": 64, "top_k": 8,
                          "nks": [(896, 2304), (2304, 896)],
                          "tokens": [16, 1024, 4096, 12288]},
}

CASES = {
    "gemm": [gemm_input(256, 256, 512), gemm_input(512, 16, 1024),
             gemm_input(64, 64, 4096)],
    "conv": [conv_input(2, 12, 12, 32, 64, 3, 3),
             conv_input(2, 8, 8, 64, 128, 1, 1)],
    "attention": [
        {"B": 2, "Hq": 4, "Hkv": 2, "Lq": 256, "Lkv": 256, "D": 64,
         "dtype_bits": 16, "causal": 1},
    ],
    "ssd": [{"B": 2, "L": 256, "H": 4, "P": 32, "S": 32, "dtype_bits": 32}],
}


def _blocks(cfg) -> str:
    return "x".join(str(cfg[k]) for k in ("bm", "bn", "bk"))


def neighbours(M: int, N: int, K: int, pick) -> list:
    """The pick with one of bm, bn, bk halved or doubled, where the result
    is legal, pads neither N nor K (the weight) beyond the pick, and runs
    as given (``ops.matmul`` shrinks a given block that exceeds its
    dimension)."""
    inputs = gemm_input(M, N, K)
    padded = lambda c: (-(-N // c["bn"]) * c["bn"], -(-K // c["bk"]) * c["bk"])
    out = []
    for key in ("bm", "bn", "bk"):
        for scale in (0.5, 2):
            cfg = {**pick, key: int(pick[key] * scale)}
            if padded(cfg) == padded(pick) and gemm_is_legal(cfg, inputs) \
                    and cfg["bm"] <= M and cfg["bn"] <= N and cfg["bk"] <= K:
                out.append(cfg)
    return out


def _tflops(fn, a, b, min_s: float = 0.1, repeats: int = 3) -> float:
    """Host clock around back-to-back calls that end in block_until_ready,
    as WallClockBackend times, with the calls doubled until they fill
    ``min_s`` (a few calls of a small GEMM would time its dispatch); the
    best of ``repeats`` such batches, since a stall of the shared host
    only ever slows one."""
    f = jax.jit(fn)
    for _ in range(2):                   # the first call compiles
        f(a, b).block_until_ready()

    def batch(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(a, b)
        out.block_until_ready()
        return time.perf_counter() - t0

    n = 1
    while batch(n) < min_s:
        n *= 2
    dt = min(batch(n) for _ in range(repeats))
    M, K = a.shape
    return 2.0 * M * K * b.shape[1] * n / dt / 1e12


def sweep() -> list:
    """TFLOP/s of every census shape, bf16, compiled on the attached TPU:
    ``FIXED_GEMM``, the shape rule's pick, each of its neighbours and
    ``jnp.dot`` (the compiler's own GEMM)."""
    rows = []
    for model, (nks, ms) in CENSUS.items():
        for N, K in nks:
            kb = jax.random.PRNGKey(N * 7 + K)
            b = jax.random.normal(kb, (K, N), jnp.float32).astype(jnp.bfloat16)
            for M in ms:
                a = jax.random.normal(jax.random.PRNGKey(M), (M, K),
                                      jnp.float32).astype(jnp.bfloat16)
                pick = gemm_default_config(M, N, K, 16)
                tried = {}
                for cfg in [FIXED_GEMM, pick] + neighbours(M, N, K, pick):
                    if _blocks(cfg) in tried:
                        continue
                    # the pick as ops.matmul takes it with no config
                    given = None if cfg is pick else cfg
                    tried[_blocks(cfg)] = _tflops(
                        lambda x, y, c=given: ops.matmul(x, y, c,
                                                         interpret=False),
                        a, b)
                dot = _tflops(jnp.dot, a, b)
                best = max(tried, key=tried.get)
                row = {"model": model, "M": M, "N": N, "K": K,
                       "fixed": round(tried[_blocks(FIXED_GEMM)], 3),
                       "pick": _blocks(pick),
                       "pick_tflops": round(tried[_blocks(pick)], 3),
                       "best": best, "best_tflops": round(tried[best], 3),
                       "dot": round(dot, 3),
                       "all": {k: round(v, 3) for k, v in tried.items()}}
                rows.append(row)
                print(" ".join(f"{k}={v}" for k, v in row.items()
                               if k != "all"), flush=True)
    print(table(rows, ["model", "M", "N", "K", "fixed", "pick",
                       "pick_tflops", "best", "best_tflops", "dot"],
                f"GEMM sweep, bf16 TFLOP/s ({jax.devices()[0].device_kind})"))
    save("kernel_sweep", {"device": jax.devices()[0].device_kind,
                          "rows": rows})
    return rows


def _seconds(fn, *args, min_s: float = 0.1, repeats: int = 3) -> float:
    """Seconds a call, timed as ``_tflops`` times."""
    f = jax.jit(fn)
    for _ in range(2):
        jax.block_until_ready(f(*args))

    def batch(n):
        t0 = time.perf_counter()
        for _ in range(n):
            out = f(*args)
        jax.block_until_ready(out)
        return time.perf_counter() - t0

    n = 1
    while batch(n) < min_s:
        n *= 2
    return min(batch(n) for _ in range(repeats)) / n


def _routed_sizes(tokens: int, experts: int, top_k: int) -> jax.Array:
    """Rows per expert of ``tokens`` routed top-k by random logits."""
    logits = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, experts))
    _, idx = jax.lax.top_k(logits, top_k)
    return jnp.bincount(idx.reshape(-1), length=experts).astype(jnp.int32)


def gmm_candidates(M: int, N: int, K: int) -> list:
    """(tm, tk, tn) tiles tried: rows 128-512 (fewer where M is small),
    the whole K and N or half of either where that stays lane-aligned."""
    tms = [t for t in (32, 64, 128, 256, 512) if t <= max(M, 128)][-3:]
    tks = [K] + ([K // 2] if (K // 2) % 128 == 0 else [])
    tns = [N] + ([N // 2] if (N // 2) % 128 == 0 else [])
    return [(tm, tk, tn) for tm in tms for tk in tks for tn in tns]


def gmm_sweep() -> list:
    """TFLOP/s of each expert GEMM of ``GMM_CENSUS`` at each token count,
    bf16, compiled on the attached TPU, with the weights as the served path
    passes them (two layers stacked, one layer's groups used): the Pallas
    grouped GEMM at each candidate tiling, the shape rule's pick and XLA's
    ragged dot; then the whole served MoE layer at decode against the
    dense-over-experts form."""
    from repro.core.space import gmm_default_tiles
    from repro.models.moe import init_moe, moe_decode, moe_dropless

    rows = []
    for model, c in GMM_CENSUS.items():
        E, k = c["experts"], c["top_k"]
        for N, K in c["nks"]:
            # as served: two layers' experts stacked and flattened, the
            # second layer's groups holding the rows (the first's empty)
            rhs = jax.random.normal(jax.random.PRNGKey(N + K), (2 * E, K, N),
                                    jnp.float32).astype(jnp.bfloat16)
            for T in c["tokens"]:
                M = T * k
                gs = jnp.zeros((2 * E,), jnp.int32).at[E:].set(
                    _routed_sizes(T, E, k))
                lhs = jax.random.normal(jax.random.PRNGKey(M), (M, K),
                                        jnp.float32).astype(jnp.bfloat16)
                flops = 2.0 * M * N * K
                tried = {}
                for tiles in gmm_candidates(M, N, K):
                    try:
                        dt = _seconds(lambda a, b, g, t=tiles:
                                      ops.grouped_matmul(a, b, g, t,
                                                         interpret=False),
                                      lhs, rhs, gs)
                        tried["x".join(map(str, tiles))] = flops / dt / 1e12
                    except Exception as e:         # refused by the compiler
                        tried["x".join(map(str, tiles))] = \
                            f"failed: {str(e)[:60]}"
                ragged = flops / _seconds(jax.lax.ragged_dot, lhs, rhs,
                                          gs) / 1e12
                ok = {t: v for t, v in tried.items() if isinstance(v, float)}
                best = max(ok, key=ok.get) if ok else None
                pick = gmm_default_tiles(M, N, K, 16)
                row = {"model": model, "T": T, "M": M, "N": N, "K": K,
                       "pick": "ragged_dot" if pick is None
                       else "x".join(map(str, pick)),
                       "best": best,
                       "best_tflops": round(ok[best], 3) if best else None,
                       "ragged_dot": round(ragged, 3),
                       "all": {t: (round(v, 3) if isinstance(v, float)
                                   else v) for t, v in tried.items()}}
                rows.append(row)
                print(" ".join(f"{k2}={v}" for k2, v in row.items()),
                      flush=True)
        # the whole layer at the decode token count
        D, F = c["nks"][0][1], c["nks"][0][0]
        p = init_moe(jax.random.PRNGKey(0), D, F, E, jnp.bfloat16)
        for T in c["tokens"][:2]:
            x = jax.random.normal(jax.random.PRNGKey(T), (T, 1, D),
                                  jnp.float32).astype(jnp.bfloat16)
            drop = _seconds(lambda pp, xx: moe_dropless(
                pp, xx, n_experts=E, top_k=k)[0], p, x)
            dense = _seconds(lambda pp, xx: moe_decode(
                pp, xx, n_experts=E, top_k=k), p, x)
            row = {"model": model, "layer_tokens": T,
                   "dropless_ms": round(1e3 * drop, 4),
                   "dense_over_experts_ms": round(1e3 * dense, 4)}
            rows.append(row)
            print(" ".join(f"{k2}={v}" for k2, v in row.items()), flush=True)
    save("gmm_sweep", {"device": jax.devices()[0].device_kind,
                       "rows": rows})
    return rows


def run(fast: bool = True) -> dict:
    interp = InterpretBackend()
    rows = []
    for space, inputs_list in CASES.items():
        tuner = get_trained_tuner(space, fast=True) if space == "gemm" \
            else None
        for inputs in inputs_list:
            if tuner is not None:
                cfg = tuner.best_config(inputs, remeasure=False)
            else:
                cfg = {"gemm": FIXED_GEMM, "conv": DEFAULT_CONV,
                       "attention": DEFAULT_ATTN, "ssd": DEFAULT_SSD}[space]
            t0 = time.time()
            tput = interp.measure(space, cfg, inputs)   # raises on mismatch
            rows.append({"kernel": space, "inputs": str(inputs)[:48],
                         "config": str({k: cfg[k] for k in list(cfg)[:4]}),
                         "allclose": "pass",
                         "sim TFLOPS": f"{tput_fmt(tput)}",
                         "check_s": f"{time.time()-t0:.1f}"})
    print(table(rows, ["kernel", "inputs", "config", "allclose",
                       "sim TFLOPS", "check_s"],
                "E7 — Pallas kernels vs jnp oracles (interpret mode)"))

    # wall-clock path: the compiled Pallas GEMM, timed on the TPU only
    if jax.default_backend() == "tpu":
        wc = WallClockBackend()
        inputs = gemm_input(512, 512, 512)
        t = wc.measure("gemm", FIXED_GEMM, inputs)
        t4 = wc.measure("gemm", {**FIXED_GEMM, "k_split": 4}, inputs)
        print(f"\nwall-clock ({jax.devices()[0].device_kind}) 512^3 bf16: "
              f"k_split=1 {t:.3f} TFLOPS, k_split=4 {t4:.3f} TFLOPS")
        sweep()
    else:
        print("\nwall-clock: not measured (no TPU attached)")
    save("kernels", {"rows": rows})
    return {"rows": rows}


def tput_fmt(x: float) -> str:
    return f"{x:.1f}"


if __name__ == "__main__":
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--sweep", action="store_true",
                   help="only the GEMM sweep of the census shapes (TPU)")
    p.add_argument("--gmm", action="store_true",
                   help="only the grouped-GEMM sweep of GMM_CENSUS (TPU)")
    args = p.parse_args()
    if args.sweep or args.gmm:
        if jax.default_backend() != "tpu":
            raise SystemExit("the sweeps time compiled kernels on a TPU")
        sweep() if args.sweep else gmm_sweep()
    else:
        run()
