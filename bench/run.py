"""Run one cell of the benchmark on the accelerator this process finds.

  python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are the ``workloads`` of ``BENCHMARK.json``.  With ``--trace 0``
the result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from the program's spans and a profiler trace.
The last line of stdout is one JSON object; progress and the numbers
compared for ``correct`` go to stderr, those last.  Exits 2 without a
result when JAX finds no TPU or fewer chips than the cell needs.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import ctypes.util  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

# glibc mallopt parameters, and the values every run serves with
M_TRIM_THRESHOLD, M_MMAP_THRESHOLD = -1, -3
MMAP_THRESHOLD_BYTES = 32 << 20     # glibc's largest dynamic threshold
TRIM_THRESHOLD_BYTES = 1 << 30


def pin_allocator() -> None:
    """Fix the thresholds, which also stops glibc from moving them."""
    libc = ctypes.CDLL(ctypes.util.find_library("c"))
    for param, value in [(M_MMAP_THRESHOLD, MMAP_THRESHOLD_BYTES),
                         (M_TRIM_THRESHOLD, TRIM_THRESHOLD_BYTES)]:
        if libc.mallopt(param, value) != 1:
            raise RuntimeError(f"mallopt({param}, {value}) refused")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness import NoDevice, run

    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), t_process=T_PROCESS)
    except NoDevice as e:
        print(f"[bench] {e}", file=sys.stderr, flush=True)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    pin_allocator()
    sys.exit(main())
