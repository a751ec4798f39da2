"""E18 — end-to-end tracing: zero-cost disabled, bounded sampled overhead,
Perfetto-loadable artifact.

The PR-9 tentpole claim, gated three ways:

  1. ZERO INSTRUMENT CALLS DISABLED — with ``trace_sample=0`` a live
     engine run (admission, router decision, decode ticks, telemetry
     drain) must invoke NO ``Tracer`` method at all.  Every call site
     reads the one module global and takes the byte-identical untraced
     path; this is proven by monkeypatch-counting ``Tracer.root`` /
     ``span`` / ``begin`` over a full ``generate``, same technique as
     E15's registry-instrument gate.

  2. SAMPLED OVERHEAD — at 1% sampling the wall time per decode tick
     (a ``generate`` call's wall time over its ticks) must stay within 2%
     of the untraced engine (budget widened by 2x the
     box's own A/A noise floor, measured from the quiet blocks of each
     quiet/traced/quiet triplet — E15's drift-cancelling methodology).

  3. ARTIFACT — a fully-traced run (sample=1.0, tunedb + router +
     measure) exports Chrome trace-event JSON to ``results/bench/`` that
     parses, carries schema v1, and contains the linked span taxonomy a
     Perfetto view needs: router decision, decode tick, dispatch tier
     resolution (with tier attribute), and a measurement.  CI uploads it.
"""

from __future__ import annotations

import json
import shutil
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.tuner import clear_tuners
from repro.kernels import dispatch
from repro.models import ModelConfig, init_params
from repro.serve import Engine, ServeConfig
from repro.tunedb import (RecordStore, TuneRecord, clear_store,
                          clear_telemetry)
from repro.tunedb.model import clear_models
from repro.tunedb.obs.trace import Tracer, enable_tracing, reset_tracing

from .common import RESULTS, save, table

OVERHEAD_THRESHOLD = 0.02       # <= 2% tick overhead at 1% sampling
SAMPLE_RATE = 0.01
ARTIFACT = "trace_E18.json"
CFG = {"bm": 64, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 1,
       "order": 0, "acc32": 1, "prefetch": 2}


def _reset() -> None:
    reset_tracing()
    clear_tuners()
    clear_store()
    clear_models()
    clear_telemetry()
    dispatch.reset_fallback_warnings()


def _small_engine(tmp: Path, **serve_kw) -> Engine:
    cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2, n_kv=1,
                      d_ff=64, vocab=64, dtype=jnp.float32, attn_chunk=16,
                      logit_chunk=16, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))
    return Engine(cfg, params, ServeConfig(max_len=64, slots=2, **serve_kw))


def _prompts(n: int = 2, length: int = 6):
    rng = np.random.default_rng(0)
    return [rng.integers(0, 64, length) for _ in range(n)]


# ---------------------------------------------------------------------------
# 1. tracing disabled: zero Tracer calls over a live engine run
# ---------------------------------------------------------------------------

def _bench_disabled(tmp: Path) -> dict:
    _reset()
    eng = _small_engine(tmp, router="round_robin", trace_sample=0.0)
    eng.generate(_prompts(), max_new=8)         # warm: compile both paths

    calls = 0

    def _counting(orig):
        def wrapped(self, *a, **kw):
            nonlocal calls
            calls += 1
            return orig(self, *a, **kw)
        return wrapped

    patched = ["root", "span", "begin"]
    originals = [(name, getattr(Tracer, name)) for name in patched]
    try:
        for name, orig in originals:
            setattr(Tracer, name, _counting(orig))
        eng.generate(_prompts(4), max_new=16)
    finally:
        for name, orig in originals:
            setattr(Tracer, name, orig)

    ticks = eng.ticks
    print(f"E18.1 — tracing disabled: {calls} Tracer calls over "
          f"{ticks} decode ticks (gate: 0)")
    return {"instrument_calls": calls, "ticks": ticks,
            "pass": calls == 0}


# ---------------------------------------------------------------------------
# 2. tick overhead at 1% sampling (quiet/traced/quiet triplets)
# ---------------------------------------------------------------------------

def _bench_overhead(fast: bool, tmp: Path) -> dict:
    _reset()
    eng = _small_engine(tmp, trace_sample=0.0)
    n_prompts, max_new = (3, 24) if fast else (6, 48)
    repeats = 9 if fast else 21

    def block(traced: bool) -> float:
        """Wall seconds per decode tick over one generate run."""
        if traced:
            eng.tracer = enable_tracing(SAMPLE_RATE)
        else:
            reset_tracing()
            eng.tracer = None
        ticks, t0 = eng.ticks, time.perf_counter()
        eng.generate(_prompts(n_prompts), max_new=max_new)
        return (time.perf_counter() - t0) / (eng.ticks - ticks)

    block(False)                            # warm both compiled paths
    block(True)
    ratios, aa = [], []
    quiet_best = traced_best = float("inf")
    # quiet/traced/quiet: the centered ratio cancels linear machine-load
    # drift; the quiet pair gives the A/A noise floor the budget widens by
    for _ in range(repeats):
        q1, s, q2 = block(False), block(True), block(False)
        ratios.append(2.0 * s / (q1 + q2))
        aa.append(abs(q2 / q1 - 1.0))
        quiet_best = min(quiet_best, q1, q2)
        traced_best = min(traced_best, s)
    reset_tracing()
    overhead = sorted(ratios)[len(ratios) // 2] - 1.0
    noise = sorted(aa)[len(aa) // 2]
    budget = OVERHEAD_THRESHOLD + 2.0 * noise

    rows = [
        {"engine loop": "untraced", "us/tick": f"{quiet_best*1e6:.0f}"},
        {"engine loop": f"traced @ {SAMPLE_RATE:.0%} sampling",
         "us/tick": f"{traced_best*1e6:.0f}"},
    ]
    print(table(rows, ["engine loop", "us/tick"],
                "E18.2 — decode tick cost under sampled tracing"))
    print(f"\nsampled-tracing overhead {overhead:+.2%} "
          f"(gate <= {OVERHEAD_THRESHOLD:.0%} + 2x the {noise:.2%} A/A "
          f"noise floor = {budget:.2%}) over {repeats} triplets")
    return {"quiet_us": quiet_best * 1e6, "traced_us": traced_best * 1e6,
            "overhead": overhead, "noise": noise, "budget": budget,
            "sample": SAMPLE_RATE, "repeats": repeats,
            "threshold": OVERHEAD_THRESHOLD,
            "pass": overhead <= budget}


# ---------------------------------------------------------------------------
# 3. the Perfetto artifact: fully-traced run, exported + validated
# ---------------------------------------------------------------------------

REQUIRED_SPANS = ("request.route", "engine.admit", "engine.tick",
                  "dispatch.resolve")


def _bench_artifact(tmp: Path) -> dict:
    _reset()
    db = tmp / "store.jsonl"
    store = RecordStore.open(db)
    from repro.core.space import gemm_input
    store.add(TuneRecord(space="gemm", inputs=gemm_input(512, 16, 2048),
                         config=dict(CFG), tflops=100.0, backend="bench",
                         source="tuner", created_at=time.time()))
    eng = _small_engine(tmp, tunedb=str(db), router="round_robin",
                        trace_sample=1.0, measure="sim")
    eng.generate(_prompts(3), max_new=12)

    out = RESULTS / ARTIFACT
    RESULTS.mkdir(parents=True, exist_ok=True)
    n = eng.tracer.export(out)
    reset_tracing()

    doc = json.loads(out.read_text())       # must parse — Perfetto will
    evs = doc.get("traceEvents", [])
    names = {e.get("name") for e in evs}
    ids = {e["args"]["span_id"] for e in evs}
    well_formed = all(e.get("ph") == "X" and "ts" in e and "dur" in e
                      and "trace_id" in e.get("args", {}) for e in evs)
    linked = sum(1 for e in evs if e["args"]["parent_id"] in ids)
    missing = [s for s in REQUIRED_SPANS if s not in names]
    has_measure = any(str(s).startswith("measure.") for s in names)
    tiers = {e["args"].get("tier") for e in evs
             if e.get("name") == "dispatch.resolve"}
    ok = (n > 0 and well_formed and not missing and has_measure
          and doc.get("otherData", {}).get("schema") == 1
          and None not in tiers and linked > 0)
    print(f"E18.3 — artifact {out.name}: {n} spans, "
          f"{linked} parent-linked, tiers {sorted(tiers)}, "
          f"span names {sorted(names)} "
          f"({'OK' if ok else 'MISSING ' + ','.join(missing)})")
    return {"artifact": str(out), "spans": n, "linked": linked,
            "well_formed": well_formed, "names": sorted(names),
            "tiers": sorted(t for t in tiers if t is not None),
            "missing": missing, "has_measure": has_measure,
            "pass": bool(ok)}


def run(fast: bool = True) -> dict:
    tmp = Path(tempfile.mkdtemp(prefix="bench_trace_"))
    try:
        disabled = _bench_disabled(tmp)
        overhead = _bench_overhead(fast, tmp)
        artifact = _bench_artifact(tmp)
    finally:
        _reset()
        shutil.rmtree(tmp, ignore_errors=True)
    out = {"disabled": disabled, "overhead": overhead,
           "artifact": artifact,
           "pass": bool(disabled["pass"] and overhead["pass"]
                        and artifact["pass"])}
    save("trace", out)
    print(f"\nE18 verdict: {'PASS' if out['pass'] else 'FAIL'}")
    return out


if __name__ == "__main__":
    run()
