"""Readings that a cell's correctness limit is set from, on the chip.

  python bench/calibrate.py --workload <cell> --seeds 1,2,3 [--calls 1]

For each seed, in one process: weights from the seed, ``--calls`` calls of
the cell's mix through the one engine (compiled once, its weights replaced
between seeds), then the sample that a run compares, read twice: the
served tokens' widest gap below the float32 reference (the program's
reading) and the gap of the tokens a float8 reference puts first (the
control's).  Each is judged at the cell's limits by the comparison that
decides a run's ``correct``: the program's as ``correct``, the control's
as ``control_correct``, which has to be false.  One JSON line per seed on
stdout.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--calls", type=int, default=1)
    args = ap.parse_args(argv)

    import jax

    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve.engine import Engine, ServeConfig

    from bench import correctness
    from bench.harness import Cell, check_device
    from bench.model import ModelSpec, round_up, seed_key
    from bench.traffic import Calls, Mix

    cell = Cell.load(args.workload)
    check_device(cell.chips)
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    spec, mix = ModelSpec.load(cell.config), Mix.load(cell.traffic)
    limits = correctness.Limits.load(cell.name)
    engine = None
    for seed in (int(s) for s in args.seeds.split(",")):
        weights = spec.make_weights(seed_key(seed))
        if engine is None:
            engine = Engine(spec.program_config(), weights,
                            ServeConfig(slots=spec.slots, max_len=spec.max_len))
        engine.params = weights
        calls = Calls(mix, spec.shape.vocab, seed)
        served = []
        t = time.perf_counter()
        for i in range(args.calls):
            prompts = calls.call(i)
            served.extend(zip(prompts, engine.generate(prompts, mix.max_new)))
        t_serve = time.perf_counter() - t
        t = time.perf_counter()
        readings = correctness.compare(
            spec, weights, served, seed, limits,
            length=round_up(mix.longest_request(), 256),
            max_new=mix.max_new, per_call=mix.requests_per_call,
            control=True)
        short = sum(len(o) != mix.max_new for _, o in served)
        readings.update(
            seed=seed, serve_s=t_serve,
            reference_and_control_s=time.perf_counter() - t, short=short,
            correct=correctness.passed(
                correctness.checks(readings, limits, short)),
            control_correct=correctness.passed(
                correctness.control_checks(readings, limits)))
        print(json.dumps(readings), flush=True)
        engine.params = None        # the next seed's weights replace these
        del weights
    return 0


if __name__ == "__main__":
    sys.exit(main())
