"""Measurement backends: the oracle that labels (config, inputs) -> TFLOPS.

The paper benchmarks 50k real kernels on a GPU (§4).  The backend is
pluggable (DESIGN.md §2), so tuning runs with or without a TPU attached:

  * :class:`SimulatedTPUBackend` — analytical TPU v5e model with exactly the
    max(latency/n, throughput) saturation structure the paper cites from
    Volkov (eq. 2-3), adapted to the TPU execution model (grid pipelining
    instead of warp occupancy, VMEM instead of shared memory, MXU alignment
    instead of warp shapes).  Deterministic given (config, inputs, seed), with
    multiplicative log-normal noise mimicking measurement jitter.
  * :class:`WallClockBackend` — times the compiled Pallas GEMM with the
    config under test on the attached TPU; raises without one.
  * :class:`InterpretBackend` — executes the actual Pallas kernel
    (interpreted off a TPU) and checks it against the jnp reference; returns the
    simulator's throughput on success, raises on numerical mismatch.  Used by
    tests to guarantee every sampled config is *runnable*, the property that
    separates X from X-hat.

All backends expose ``measure(space_name, cfg, inputs) -> float`` (TFLOPS,
following the paper's choice of performance metric).
"""

from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from typing import Mapping

from .space import conv_out_shape

# ---------------------------------------------------------------------------
# TPU v5e hardware constants (the TARGET; the grading constants of the task).
# ---------------------------------------------------------------------------
PEAK_BF16_TFLOPS = 197.0            # per chip
PEAK_FP32_TFLOPS = PEAK_BF16_TFLOPS / 4.0   # MXU fp32 passes
HBM_GBPS = 819.0                    # per chip
ICI_GBPS = 50.0                     # per link per direction
VMEM_BYTES = 128 * 1024 * 1024
MXU = 128                           # systolic dimension
NUM_CORES = 1                       # v5e: one TensorCore per chip
DMA_ENGINES = 4                     # independent HBM DMA channels per core
DMA_ISSUE_US = 0.15                 # serial issue->data latency per DMA chain
GRID_STEP_OVERHEAD_US = 0.05        # scalar-core bookkeeping per grid step
KERNEL_LAUNCH_US = 2.0


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


def _align_eff(x: int, tile: int) -> float:
    """Fraction of a padded tile that is useful work (remainder handling).

    The paper handles remainders with PTX predication (§8.3, 2% overhead);
    Pallas masks via pl.when on padded blocks — the cost is that the last
    block computes on padding.
    """
    padded = _ceil_div(x, tile) * tile
    return x / padded


@dataclasses.dataclass
class SimulatedTPUBackend:
    """Analytical TPU v5e performance model (Volkov eq. 2-3 structure).

    The model computes, per kernel configuration:
      t_compute — MXU time for the useful+padding FLOPs of the tiling
      t_memory  — HBM traffic time for the block schedule (incl. split-K
                  partial materialization: the paper's "diminished write
                  bandwidth" for K_G > 1)
      t         — max(t_compute, t_memory) / pipeline_efficiency
    where pipeline_efficiency saturates with the number of grid steps exactly
    like eq. (2) saturates with occupancy n: few steps => the double-buffered
    DMA pipeline never hides the fill latency.
    """

    noise: float = 0.05         # log-normal sigma; 0 => deterministic
    seed: int = 0

    # -- public API -----------------------------------------------------------
    def measure(self, space_name: str, cfg: Mapping[str, int],
                inputs: Mapping[str, int]) -> float:
        if space_name == "gemm":
            flops, t_us = self._gemm_time_us(cfg, inputs)
        elif space_name == "conv":
            flops, t_us = self._conv_time_us(cfg, inputs)
        elif space_name == "attention":
            flops, t_us = self._attention_time_us(cfg, inputs)
        elif space_name == "ssd":
            flops, t_us = self._ssd_time_us(cfg, inputs)
        else:
            raise ValueError(space_name)
        tflops = flops / (t_us * 1e-6) / 1e12
        if self.noise > 0:
            tflops *= self._jitter(space_name, cfg, inputs)
        return float(tflops)

    def time_us(self, space_name: str, cfg: Mapping[str, int],
                inputs: Mapping[str, int]) -> float:
        fn = {"gemm": self._gemm_time_us, "conv": self._conv_time_us,
              "attention": self._attention_time_us, "ssd": self._ssd_time_us}
        return fn[space_name](cfg, inputs)[1]

    # -- deterministic pseudo-noise -------------------------------------------
    def _jitter(self, space_name, cfg, inputs) -> float:
        key = json_key(space_name, cfg, inputs, self.seed)
        h = int(hashlib.sha256(key.encode()).hexdigest()[:16], 16)
        u = (h % 10**9) / 10**9
        # Box-Muller single sample
        z = math.sqrt(-2 * math.log(max(u, 1e-9))) * math.cos(
            2 * math.pi * ((h >> 32) % 10**9) / 10**9)
        return math.exp(self.noise * z)

    # -- shared machinery -------------------------------------------------
    def _combine(self, t_compute_s: float, t_memory_s: float,
                 n_steps: int, prefetch: int) -> float:
        """Eq.(3) analogue with eq.(2)'s saturation.

        prefetch>=2 overlaps copies with compute: t = max(...) divided by a
        fill-amortization term n/(n + prefetch - 1) — a grid with few
        sequential steps never amortizes the pipeline fill (the TPU twin of
        low-occupancy latency exposure).  prefetch=1 serializes copy/compute:
        t = sum(...), the un-overlapped Volkov limit.
        """
        if prefetch <= 1:
            return t_compute_s + t_memory_s
        eff = n_steps / (n_steps + (prefetch - 1))
        return max(t_compute_s, t_memory_s) / eff

    def _dma_latency_us(self, n_steps: int, prefetch: int,
                        split: int) -> float:
        """Serial DMA-issue chain cost — the TPU-native analogue of the
        paper's occupancy-based latency hiding (DESIGN.md §3).

        Grid steps issue their slab DMAs in a serial dependency chain,
        `prefetch` outstanding at a time.  Reduction splitting (the paper's
        K_G/K_L) creates `split` *independent* accumulation chains whose DMAs
        interleave across the core's DMA engines — more outstanding requests,
        better HBM latency hiding, exactly the paper's 'reduction splitting
        improves latency hiding', re-derived for the DMA pipeline instead of
        warp occupancy.
        """
        outstanding = max(prefetch, 1) * min(max(split, 1), DMA_ENGINES)
        return n_steps * DMA_ISSUE_US / outstanding

    def _mxu_eff(self, bm: int, bn: int, bk: int, dtype_bits: int) -> float:
        """MXU utilization of one block-matmul: penalize tiles that do not
        fill the 128x128 systolic array or starve its pipeline depth."""
        eff_m = min(1.0, bm / MXU)
        eff_n = min(1.0, bn / MXU)
        # short K passes can't keep the systolic pipeline full
        eff_k = bk / (bk + MXU / 4)
        # fp32 runs as multi-pass on the MXU but with the same efficiency shape
        return eff_m * eff_n * eff_k

    def _peak_tflops(self, dtype_bits: int) -> float:
        return PEAK_BF16_TFLOPS if dtype_bits <= 16 else PEAK_FP32_TFLOPS

    # -- GEMM ------------------------------------------------------------
    def _gemm_time_us(self, cfg, inputs):
        M, N, K = inputs["M"], inputs["N"], inputs["K"]
        bits = inputs["dtype_bits"]
        bpe = bits // 8
        bm, bn, bk = cfg["bm"], cfg["bn"], cfg["bk"]
        ks = cfg["k_split"]

        gm, gn = _ceil_div(M, bm), _ceil_div(N, bn)
        k_steps = _ceil_div(K, bk)
        k_per_split = _ceil_div(k_steps, ks)
        n_steps = gm * gn * ks * k_per_split    # total grid steps

        useful_flops = 2.0 * M * N * K
        # padded tiles still occupy the MXU
        pad = (_align_eff(M, bm) * _align_eff(N, bn) * _align_eff(K, bk))
        mxu = self._mxu_eff(bm, bn, bk, bits)
        # transposed operands need an in-VMEM relayout pass before the MXU;
        # the paper's §7 backward benchmarks show exactly this cost on GPU.
        trans_pen = 1.0
        if inputs.get("trans_a"):
            trans_pen *= 0.92
        if inputs.get("trans_b"):
            trans_pen *= 0.96
        # k_unroll: >1 exposes ILP to the Mosaic scheduler; saturates fast.
        unroll = cfg.get("k_unroll", 1)
        ilp = 1.0 - 0.06 / unroll
        peak = self._peak_tflops(bits) * 1e12
        t_compute_s = useful_flops / (pad * max(peak * mxu * trans_pen * ilp, 1e9))

        # HBM traffic: every (m,n) block re-reads its A/B slabs per k step;
        # an output block is written once per split (split-K materializes
        # k_split partials + a reduction pass that re-reads them).
        a_bytes = gm * gn * ks * k_per_split * (bm * bk * bpe)
        b_bytes = gm * gn * ks * k_per_split * (bk * bn * bpe)
        # grid-order-dependent L2-ish reuse of B slabs (order=0: m-major
        # revisits B; order=1 revisits A).  TPUs have no L2; this models
        # XLA/Mosaic keeping the revisited slab resident in VMEM across
        # consecutive grid steps.
        if cfg.get("order", 0) == 0 and gm > 1:
            b_bytes *= 0.65
        elif cfg.get("order", 0) == 1 and gn > 1:
            a_bytes *= 0.65
        acc_bpe = 4 if cfg.get("acc32", 1) else bpe
        out_bytes = M * N * bpe
        if ks > 1:
            # write ks partials + re-read them in the reduction pass (the
            # paper's 'diminished write bandwidth' for K_G > 1, TPU-style:
            # materialized partials, no atomics).
            out_bytes = 2 * ks * M * N * acc_bpe + M * N * bpe
        lat_us = self._dma_latency_us(n_steps, cfg.get("prefetch", 2), ks)
        t_memory_s = ((a_bytes + b_bytes + out_bytes) / (HBM_GBPS * 1e9)
                      + lat_us * 1e-6)

        t_s = self._combine(t_compute_s, t_memory_s, n_steps,
                            cfg.get("prefetch", 2))
        t_us = (t_s * 1e6 + KERNEL_LAUNCH_US
                + n_steps * GRID_STEP_OVERHEAD_US)
        return useful_flops, t_us

    # -- CONV (implicit GEMM) ---------------------------------------------
    def _conv_time_us(self, cfg, inputs):
        P, Q = conv_out_shape(inputs)
        Nb, C, Kf = inputs["N"], inputs["C"], inputs["K"]
        R, S = inputs["R"], inputs["S"]
        bits = inputs["dtype_bits"]
        bpe = bits // 8
        npq = Nb * P * Q

        b_npq, b_k, b_c = cfg["b_npq"], cfg["b_k"], cfg["b_c"]
        cs = cfg["c_split"]
        g_npq, g_k = _ceil_div(npq, b_npq), _ceil_div(Kf, b_k)
        c_steps = _ceil_div(C, b_c)
        c_per_split = _ceil_div(c_steps, cs)
        rs_inner = _ceil_div(R * S, cfg["rs_unroll"]) * cfg["rs_unroll"]
        n_steps = g_npq * g_k * cs * c_per_split

        useful_flops = 2.0 * npq * Kf * C * R * S
        pad = (_align_eff(npq, b_npq) * _align_eff(Kf, b_k)
               * _align_eff(C, b_c) * (R * S) / rs_inner)
        mxu = self._mxu_eff(b_npq, b_k, b_c, bits)
        peak = self._peak_tflops(bits) * 1e12
        unroll = cfg.get("rs_unroll", 1)
        ilp = 1.0 - 0.06 / unroll
        t_compute_s = useful_flops / (pad * max(peak * mxu * ilp, 1e9))

        # input slab must include the (r,s) halo; shifted-window reuses it
        i_bytes = n_steps * b_npq * b_c * bpe * 1.15      # 15% halo overhead
        f_bytes = n_steps * b_c * rs_inner * b_k * bpe / max(R * S / rs_inner, 1)
        acc_bpe = 4 if cfg.get("acc32", 1) else bpe
        out_bytes = npq * Kf * bpe
        if cs > 1:
            out_bytes = 2 * cs * npq * Kf * acc_bpe + npq * Kf * bpe
        lat_us = self._dma_latency_us(n_steps, cfg.get("prefetch", 2), cs)
        t_memory_s = ((i_bytes + f_bytes + out_bytes) / (HBM_GBPS * 1e9)
                      + lat_us * 1e-6)

        t_s = self._combine(t_compute_s, t_memory_s, n_steps,
                            cfg.get("prefetch", 2))
        t_us = (t_s * 1e6 + KERNEL_LAUNCH_US
                + n_steps * GRID_STEP_OVERHEAD_US)
        return useful_flops, t_us

    # -- Flash attention ----------------------------------------------------
    def _attention_time_us(self, cfg, inputs):
        B, Hq, Lq, Lkv, D = (inputs["B"], inputs["Hq"], inputs["Lq"],
                             inputs["Lkv"], inputs["D"])
        bits = inputs["dtype_bits"]
        bpe = bits // 8
        bq, bkv = cfg["b_q"], cfg["b_kv"]
        causal = bool(inputs.get("causal", 0))

        frac = 0.5 if causal and Lq == Lkv else 1.0
        useful_flops = 4.0 * B * Hq * Lq * Lkv * D * frac
        g_q = _ceil_div(Lq, bq)
        g_kv = _ceil_div(Lkv, bkv)
        n_steps = B * Hq * g_q * max(int(g_kv * frac), 1)

        pad = _align_eff(Lq, bq) * _align_eff(Lkv, bkv)
        mxu = self._mxu_eff(bq, D, bkv, bits) ** 0.5   # two chained matmuls
        peak = self._peak_tflops(bits) * 1e12
        # softmax runs on the VPU in parallel but bounds small-D efficiency
        vpu_tax = D / (D + 32)
        t_compute_s = useful_flops / (pad * max(peak * mxu * vpu_tax, 1e9))

        q_bytes = B * Hq * Lq * D * bpe
        kv_bytes = 2 * B * inputs["Hkv"] * Lkv * D * bpe * g_q * frac
        o_bytes = B * Hq * Lq * D * bpe
        lat_us = self._dma_latency_us(n_steps, cfg.get("prefetch", 2), 1)
        t_memory_s = ((q_bytes + kv_bytes + o_bytes) / (HBM_GBPS * 1e9)
                      + lat_us * 1e-6)

        t_s = self._combine(t_compute_s, t_memory_s, max(g_kv, 1),
                            cfg.get("prefetch", 2))
        t_us = t_s * 1e6 + KERNEL_LAUNCH_US + n_steps * 0.02
        return useful_flops, t_us

    # -- Mamba-2 SSD chunk scan ----------------------------------------------
    def _ssd_time_us(self, cfg, inputs):
        B, L, H, P, S = (inputs["B"], inputs["L"], inputs["H"], inputs["P"],
                         inputs["S"])
        bits = inputs["dtype_bits"]
        bpe = bits // 8
        c, bh = cfg["chunk"], cfg["b_heads"]
        n_chunks = _ceil_div(L, c)

        # SSD: intra-chunk quadratic attention-like term + inter-chunk state
        intra = 2.0 * B * H * n_chunks * c * c * (P + S)
        inter = 2.0 * B * H * n_chunks * (c * S * P * 2 + P * S)
        useful_flops = intra + inter
        pad = _align_eff(L, c)
        mxu = self._mxu_eff(c, P, S, bits)
        peak = self._peak_tflops(bits) * 1e12
        t_compute_s = useful_flops / (pad * max(peak * mxu, 1e9))

        x_bytes = B * H * L * P * bpe * 2
        bc_bytes = 2 * B * L * S * bpe
        state_bytes = B * H * n_chunks * P * S * 4    # carried in fp32
        steps = B * _ceil_div(H, bh) * n_chunks
        lat_us = self._dma_latency_us(steps, cfg.get("prefetch", 2), bh)
        t_memory_s = ((x_bytes + bc_bytes + state_bytes) / (HBM_GBPS * 1e9)
                      + lat_us * 1e-6)

        t_s = self._combine(t_compute_s, t_memory_s, max(n_chunks, 1),
                            cfg.get("prefetch", 2))
        t_us = t_s * 1e6 + KERNEL_LAUNCH_US + steps * 0.02
        return useful_flops, t_us


@dataclasses.dataclass
class WallClockBackend:
    """Times the compiled Pallas GEMM, with the config under test, on the
    attached TPU.

    Every tuning parameter reaches the kernel (``ops.matmul(...,
    interpret=False)``), so the label is that config's own time.  Without a
    TPU it raises: a host clock around XLA:CPU or the Pallas interpreter
    is no measurement of the kernel.
    """

    warmup: int = 1
    iters: int = 3

    def measure(self, space_name: str, cfg: Mapping[str, int],
                inputs: Mapping[str, int]) -> float:
        import jax
        import jax.numpy as jnp

        from repro.kernels import ops

        if jax.default_backend() != "tpu":
            raise RuntimeError(
                "WallClockBackend times compiled kernels on a TPU; JAX's "
                f"backend here is {jax.default_backend()!r}")
        if space_name != "gemm":
            raise NotImplementedError("WallClockBackend covers GEMM")
        M, N, K = inputs["M"], inputs["N"], inputs["K"]
        dtype = jnp.bfloat16 if inputs["dtype_bits"] <= 16 else jnp.float32
        ka, kb = jax.random.split(jax.random.PRNGKey(0))
        a = jax.random.normal(ka, (M, K), jnp.float32).astype(dtype)
        b = jax.random.normal(kb, (K, N), jnp.float32).astype(dtype)
        cfg = dict(cfg)
        jf = jax.jit(lambda a, b: ops.matmul(a, b, cfg, interpret=False))
        for _ in range(1 + self.warmup):     # the first call compiles
            jf(a, b).block_until_ready()
        t0 = time.perf_counter()
        for _ in range(self.iters):
            out = jf(a, b)
        out.block_until_ready()
        dt = (time.perf_counter() - t0) / self.iters
        return 2.0 * M * N * K / dt / 1e12


@dataclasses.dataclass
class InterpretBackend:
    """Correctness oracle: run the real Pallas kernel (interpreted off a TPU)
    vs ref.

    Throughput cannot be measured in interpret mode; on success returns the
    simulator's estimate so the tuning loop composes, on numerical mismatch
    raises AssertionError — tests use this to certify sampled configs are in X.
    """

    sim: SimulatedTPUBackend = dataclasses.field(
        default_factory=lambda: SimulatedTPUBackend(noise=0.0))
    rtol: float = 2e-2

    def measure(self, space_name: str, cfg: Mapping[str, int],
                inputs: Mapping[str, int]) -> float:
        from repro.kernels import dispatch
        dispatch.check_config(space_name, dict(cfg), dict(inputs),
                              rtol=self.rtol)
        return self.sim.measure(space_name, cfg, inputs)


def json_key(space_name: str, cfg: Mapping[str, int],
             inputs: Mapping[str, int], seed: int = 0) -> str:
    import json
    return json.dumps({"s": space_name, "c": dict(sorted(cfg.items())),
                       "i": dict(sorted(inputs.items())), "seed": seed},
                      sort_keys=True)
