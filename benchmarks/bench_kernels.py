"""E7 — kernel harness: every Pallas kernel validated (interpret mode off
a TPU) against its ref.py oracle on tuner-selected configurations, plus
the compiled GEMM's wall clock when a TPU is attached."""

from __future__ import annotations

import time

import jax

from repro.core.backend import InterpretBackend, WallClockBackend
from repro.core.space import conv_input, gemm_input
from repro.kernels.ops import (DEFAULT_ATTN, DEFAULT_CONV, DEFAULT_GEMM,
                               DEFAULT_SSD)
from .common import get_trained_tuner, save, table


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
                cfg = {"gemm": DEFAULT_GEMM, "conv": DEFAULT_CONV,
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
        t = wc.measure("gemm", DEFAULT_GEMM, inputs)
        t4 = wc.measure("gemm", {**DEFAULT_GEMM, "k_split": 4}, inputs)
        print(f"\nwall-clock ({jax.devices()[0].device_kind}) 512^3 bf16: "
              f"k_split=1 {t:.3f} TFLOPS, k_split=4 {t4:.3f} TFLOPS")
    else:
        print("\nwall-clock: not measured (no TPU attached)")
    save("kernels", {"rows": rows})
    return {"rows": rows}


def tput_fmt(x: float) -> str:
    return f"{x:.1f}"


if __name__ == "__main__":
    run()
