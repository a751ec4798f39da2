"""One run of one cell: set-up, the measured window, the traced reading of
the per-layer metrics, and the check of what was served.

The program under test is ``repro.serve.engine.Engine.generate``, driven in
a closed loop (MLPerf Inference's Offline scenario): the next call starts
when the last returns.  From the program the benchmark takes only the
engine, its spans (``engine.admit``, ``engine.prefill``, ``engine.tick``)
and its compile cache location.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import pathlib
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Tuple

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCHMARK = ROOT / "BENCHMARK.json"


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class NoDevice(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    @classmethod
    def load(cls, name: str) -> "Cell":
        bench = json.loads(BENCHMARK.read_text())
        for w in bench["workloads"]:
            if w["name"] == name:
                break
        else:
            raise KeyError(f"no workload {name!r} in {BENCHMARK.name}")
        return cls(name=name, config=w["config"], traffic=w["traffic"],
                   chips=int(w["chips"]), end_to_end=bench["end_to_end"],
                   per_layer=bench["per_layer"])


def check_device(chips: int) -> Dict[str, Any]:
    import jax

    devs = jax.devices()
    found = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    say(f"device platform={found['platform']} kind={found['kind']} "
        f"count={found['count']}")
    if found["platform"] != "tpu":
        raise NoDevice(f"JAX found no TPU: {found}")
    if found["count"] < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found "
                       f"{found['count']}")
    return found


class CompileCounter:
    """Counts XLA compilations and jit traces while ``on``."""

    def __init__(self) -> None:
        import jax

        self.on, self.compiles, self.traces = False, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, name: str, _secs: float, **_kw) -> None:
        if not self.on:
            return
        if name.endswith("backend_compile_duration"):
            self.compiles += 1
        elif name.endswith("jaxpr_trace_duration"):
            self.traces += 1


def _drain_spans(tracer, into: List) -> None:
    from .observe import HostSpan

    for s in tracer.spans():
        if s.name.startswith("engine."):
            into.append(HostSpan(s.name, s.t0, s.dur, dict(s.attrs)))
    tracer.clear()


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_process: float, engine_factory=None) -> Dict[str, Any]:
    """The result line of one run.  ``engine_factory(cfg, params, sc)``
    replaces the program's Engine, for tests that break the timed path."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    from . import correctness
    from .model import ModelSpec, round_up, seed_key
    from .observe import Observations, Request, read_metric
    from .peaks import peak_for
    from .traffic import Calls, Mix

    cell = Cell.load(cell_name)
    device = check_device(cell.chips)
    cache_dir = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    say(f"compile cache {cache_dir}")
    spec = ModelSpec.load(cell.config)
    mix = Mix.load(cell.traffic)
    limits = correctness.Limits.load(cell.name)
    peak = peak_for(device["kind"])
    counter = CompileCounter()

    from repro.serve.engine import Engine, ServeConfig
    factory = engine_factory or Engine
    weights = spec.make_weights(seed_key(seed))
    jax.block_until_ready(weights)
    tracer = None
    if trace:
        from repro.tunedb.obs.trace import enable_tracing
        tracer = enable_tracing(1.0, max_spans=1_000_000)
    engine = factory(spec.program_config(), weights,
                     ServeConfig(slots=spec.slots, max_len=spec.max_len,
                                 trace_sample=1.0 if trace else 0.0))
    calls = Calls(mix, spec.shape.vocab, seed)
    # warm-up: one prompt of every length the mix sends, and decode
    warm = calls.call(0)
    by_len = {len(p): p for p in warm}
    engine.generate([by_len[n] for n in sorted(by_len)], max_new=2)
    if tracer is not None:
        tracer.clear()
    say(f"traffic {calls.stats()} max_new={mix.max_new}")

    spans: List = []
    served: List[Tuple[Any, List[int]]] = []
    tracewin = None
    if trace:
        from .devtrace import TraceWindow
        log_dir = tempfile.mkdtemp(prefix="bench-trace-")
        tracewin = TraceWindow(log_dir, delay_s=0.25 * seconds,
                               length_s=min(4.0, 0.5 * seconds))
    counter.on = True
    t0 = time.perf_counter()
    setup_s = t0 - t_process
    if tracewin is not None:
        tracewin.start()
    i, call_s = 0, []
    while time.perf_counter() - t0 < seconds:
        prompts = calls.call(i)
        tc = time.perf_counter()
        outs = engine.generate(prompts, max_new=mix.max_new)
        call_s.append(time.perf_counter() - tc)
        served.extend(zip(prompts, outs))
        if tracer is not None:
            _drain_spans(tracer, spans)
        i += 1
    t1 = time.perf_counter()
    counter.on = False
    say(f"window {t1 - t0:.3f} s, {i} calls of "
        f"{' '.join(f'{c:.3f}' for c in call_s)} s, compiles in window "
        f"{counter.compiles}, traces in window {counter.traces}, "
        f"load average {os.getloadavg()[0]:.2f} on {os.cpu_count()} cpus")
    if tracer is not None and tracer.overflow:
        raise RuntimeError(f"{tracer.overflow} spans lost")

    attempted = len(served)
    short = sum(len(o) != mix.max_new for _, o in served)
    out_tokens = sum(len(o) for _, o in served)
    stats = jax.devices()[0].memory_stats() or {}
    device["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))

    metrics: Dict[str, Dict[str, Any]] = {}
    breakdown = None
    if not trace:
        values = {"output_tok_s": out_tokens / (t1 - t0), "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        from .devtrace import attribute_gaps, top_ops
        dtrace = tracewin.join()
        shutil.rmtree(log_dir, ignore_errors=True)
        device["busy_s"] = dtrace.busy_s()
        device["window_s"] = dtrace.window_s
        obs = Observations(
            spec=spec, peak=peak, window=(t0, t1),
            requests=[Request(len(p), len(o)) for p, o in served],
            spans=spans, trace=dtrace)
        for m in cell.per_layer:
            v = read_metric(m["name"], obs)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        lo, hi = dtrace.window
        inside = [s for s in spans if s.t1 > lo and s.t0 < hi]
        breakdown = {"device_ops": top_ops(dtrace.ops),
                     "idle_gaps": attribute_gaps(dtrace.idle_gaps(0), inside,
                                                 dtrace.ops)}

    # the program's state goes before the reference runs
    del engine
    gc.collect()
    t_ref = time.perf_counter()
    readings = correctness.compare(
        spec, weights, served, seed, limits,
        length=round_up(mix.longest_request(), 256), max_new=mix.max_new,
        per_call=mix.requests_per_call)
    say(f"reference {time.perf_counter() - t_ref:.3f} s over "
        f"{int(readings['tokens_compared'])} served tokens")
    checked = correctness.checks(readings, limits, short)
    result = {"correct": correctness.passed(checked), "attempted": attempted,
              "failed": short, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    for name, c in checked.items():
        say(f"check {name} = {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checked
    return result
