"""End-to-end request tracing + wall-clock profiling (PR 9).

Pins the tracing contracts: deterministic stride sampling with an
always-keep escape for explicit trace ids; lock-free ring completion and
bounded retention; Chrome trace-event export that Perfetto can load;
torn/partial span files skipped (never raised) by the fleet exporter; a
coordinator-published job's trace id showing up on the worker's session
spans after the merge; and the acceptance trace — one live Engine run
whose export contains linked spans for a router decision, a decode tick,
a dispatch tier resolution (with tier attribute), and a §6 measurement.
"""

import json
import threading
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.backend import WallClockBackend
from repro.core.space import gemm_input
from repro.core.tuner import clear_tuners
from repro.kernels import dispatch
from repro.models import ModelConfig, init_params
from repro.serve import Engine, ServeConfig
from repro.tunedb import (RecordStore, TuneRecord, clear_store,
                          clear_telemetry)
from repro.tunedb.fleet import Coordinator, FleetJob, Worker
from repro.tunedb.measure import MeasureQueue, ServingMeasurer
from repro.tunedb.model import clear_models
from repro.tunedb.obs import StatusServer, status_snapshot
from repro.tunedb.obs.metrics import get_registry, reset_metrics
from repro.tunedb.obs.trace import (FLEET_TRACE_DIR, Span, Tracer,
                                    collect_fleet_spans, enable_tracing,
                                    get_tracer, load_span_file,
                                    new_trace_id, reset_tracing,
                                    summarize_spans)
from repro.tunedb.__main__ import main as tunedb_main

CFG = {"bm": 64, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 1,
       "order": 0, "acc32": 1, "prefetch": 2}


@pytest.fixture(autouse=True)
def _clean_globals():
    def reset():
        reset_tracing()
        reset_metrics()
        clear_tuners()
        clear_store()
        clear_models()
        clear_telemetry()
        dispatch.reset_fallback_warnings()
    reset()
    yield
    reset()


def _rec(m, n, k, *, backend="test", tflops=100.0):
    return TuneRecord(space="gemm", inputs=gemm_input(m, n, k),
                      config=dict(CFG), tflops=tflops, backend=backend,
                      source="tuner", created_at=0.0)


# ---------------------------------------------------------------------------
# tracer core: sampling, nesting, rings, retention
# ---------------------------------------------------------------------------

def test_stride_sampling_is_deterministic():
    tr = Tracer(sample=0.5)                 # stride 2: every 2nd root kept
    kept = []
    for i in range(10):
        with tr.root("r", i=i) as sp:
            kept.append(sp is not None)
    assert kept == [False, True] * 5        # reproducible, not random
    assert tr.sampled == 5 and tr.dropped == 5
    assert all(sp.attrs["i"] % 2 == 1 for sp in tr.spans())


def test_explicit_trace_id_bypasses_sampling():
    tr = Tracer(sample=0.0)                 # stride 0: drop every minted root
    with tr.root("dropped") as sp:
        assert sp is None
    tid = new_trace_id()
    with tr.root("adopted", trace_id=tid) as sp:
        assert sp is not None and sp.trace_id == tid
    spans = tr.spans()
    assert [s.name for s in spans] == ["adopted"]


def test_child_spans_nest_and_link():
    tr = Tracer(sample=1.0)
    with tr.root("parent") as root:
        with tr.span("child", tier="exact") as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    names = {s.name for s in tr.spans()}
    assert names == {"parent", "child"}


def test_span_without_open_root_is_shared_noop():
    tr = Tracer(sample=1.0)
    a = tr.span("orphan")
    b = tr.span("orphan2")
    assert a is b                           # one shared _NULL_SPAN instance
    with a as sp:
        assert sp is None
    assert tr.spans() == []                 # nothing recorded


def test_unsampled_root_suppresses_children():
    tr = Tracer(sample=0.0)
    with tr.root("r") as sp:
        assert sp is None
        with tr.span("child") as c:
            assert c is None                # no context pushed -> no-op
    assert tr.spans() == []


def test_detached_begin_end_crosses_threads():
    tr = Tracer(sample=1.0)
    sp = tr.begin("retune.epoch", trace_id=new_trace_id(), spaces="gemm")
    t = threading.Thread(target=lambda: tr.end(sp, outcome="swapped"))
    t.start()
    t.join()
    [got] = tr.spans()
    assert got.name == "retune.epoch"
    assert got.attrs["outcome"] == "swapped" and got.dur >= 0.0


def test_rings_drain_from_worker_threads():
    tr = Tracer(sample=1.0)

    def work():
        for _ in range(50):
            with tr.root("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert tr.buffered() == 200             # finished spans sit in rings
    assert len(tr.spans()) == 200           # spans() drains them all
    assert tr.buffered() == 0


def test_retention_cap_evicts_and_counts_overflow():
    tr = Tracer(sample=1.0, max_spans=10)
    for _ in range(25):
        with tr.root("r"):
            pass
    assert len(tr.spans()) == 10
    assert tr.stats()["overflow"] > 0


def test_stats_shape():
    tr = Tracer(sample=0.25)
    st = tr.stats()
    for key in ("enabled", "sample", "sampled", "dropped", "spans",
                "buffered", "overflow", "max_spans", "tiers"):
        assert key in st
    assert st["enabled"] is True and st["sample"] == 0.25


def test_tier_latency_attribution():
    tr = Tracer(sample=1.0)
    for tier in ("plan", "plan", "model"):
        with tr.root("t"):
            with tr.span("dispatch.resolve", tier=tier, space="gemm"):
                pass
    tiers = tr.tier_latency()
    assert tiers["plan"]["count"] == 2 and tiers["model"]["count"] == 1
    assert tiers["plan"]["mean_us"] >= 0.0


# ---------------------------------------------------------------------------
# export + torn-tolerant loading
# ---------------------------------------------------------------------------

def test_chrome_export_round_trips(tmp_path):
    tr = Tracer(sample=1.0)
    with tr.root("engine.tick", tick=3):
        with tr.span("dispatch.resolve", tier="exact"):
            pass
    out = tmp_path / "trace.json"
    assert tr.export(out) == 2
    doc = json.loads(out.read_text())
    assert doc["otherData"]["schema"] == 1
    evs = doc["traceEvents"]
    assert len(evs) == 2
    for ev in evs:
        assert ev["ph"] == "X" and ev["cat"] == "tunedb"
        assert ev["ts"] >= 0.0 and ev["dur"] >= 0.0
        assert ev["args"]["trace_id"]
    # parent links survive the round trip through the Chrome doc
    back = load_span_file(out)
    by_name = {s.name: s for s in back}
    assert (by_name["dispatch.resolve"].parent_id
            == by_name["engine.tick"].span_id)


def test_export_jsonl_clears_retention(tmp_path):
    tr = Tracer(sample=1.0)
    with tr.root("a"):
        pass
    p = tmp_path / "w.jsonl"
    assert tr.export_jsonl(p) == 1
    assert tr.spans() == []                 # dump moved them out
    with tr.root("b"):
        pass
    assert tr.export_jsonl(p) == 1          # appends, no duplicates
    assert [s.name for s in load_span_file(p)] == ["a", "b"]


def test_torn_jsonl_line_is_skipped_not_raised(tmp_path):
    good = Span("fleet.job", "t1", "s1")
    good.t0, good.dur = 1.0, 0.5
    p = tmp_path / "w.jsonl"
    p.write_text(json.dumps(good.to_json()) + "\n"
                 + '{"name": "fleet.job", "trace_id": "t2", "spa')
    spans = load_span_file(p)               # crashed worker mid-write
    assert [s.trace_id for s in spans] == ["t1"]


def test_torn_chrome_document_is_skipped_whole(tmp_path):
    p = tmp_path / "t.json"
    p.write_text('{"traceEvents": [{"name": "x", "ph": "X", "ts"')
    assert load_span_file(p) == []          # mid-rename file: drop it
    p.write_text("\x00\x01 not json at all")
    assert load_span_file(p) == []
    assert load_span_file(tmp_path / "missing.json") == []


def test_collect_fleet_spans_merges_and_survives_junk(tmp_path):
    traces = tmp_path / FLEET_TRACE_DIR
    traces.mkdir()
    sp = Span("fleet.job", "tid9", "s1")
    sp.t0, sp.dur = 1.0, 0.1
    (traces / "w1.jsonl").write_text(json.dumps(sp.to_json()) + "\n")
    (traces / "w2.jsonl").write_text('{"torn')
    (traces / "w3.json").write_text("garbage")
    (traces / "notes.txt").write_text("ignored: wrong suffix")
    spans = collect_fleet_spans(tmp_path)
    assert [s.trace_id for s in spans] == ["tid9"]
    assert collect_fleet_spans(tmp_path / "nofleet") == []


def test_summarize_spans_counts_names_traces_tiers():
    tr = Tracer(sample=1.0)
    with tr.root("engine.tick"):
        with tr.span("dispatch.resolve", tier="nearest"):
            pass
    with tr.root("engine.tick"):
        pass
    s = summarize_spans(tr.spans())
    assert s["spans"] == 3 and s["traces"] == 2
    assert s["names"]["engine.tick"]["count"] == 2
    assert s["tiers"]["nearest"]["count"] == 1


# ---------------------------------------------------------------------------
# process-global enable/reset
# ---------------------------------------------------------------------------

def test_enable_tracing_installs_and_retunes_sample():
    assert get_tracer() is None
    tr = enable_tracing(1.0)
    assert get_tracer() is tr
    assert enable_tracing(0.25) is tr       # same tracer, new stride
    assert tr.sample == 0.25
    reset_tracing()
    assert get_tracer() is None


# ---------------------------------------------------------------------------
# fleet propagation: job trace id -> worker session spans -> merge
# ---------------------------------------------------------------------------

class _StubTuner:
    """Instant deterministic tuner; fleet tracing is about propagation,
    not search quality."""

    space = None
    backend = None

    def __init__(self):
        from repro.core.backend import SimulatedTPUBackend
        from repro.core.space import GEMM_SPACE
        self.space = GEMM_SPACE
        self.backend = SimulatedTPUBackend(noise=0.0)

    def search(self, inputs, remeasure=True):
        from repro.core.search import SearchResult
        cfg = dict(CFG)
        tf = float(self.backend.measure("gemm", cfg, inputs))
        return SearchResult(best=cfg, predicted_tflops=tf,
                            measured_tflops=tf, top_k=[(cfg, tf)],
                            n_candidates=1, measured=[(cfg, tf)])


def test_job_trace_id_reaches_worker_spans_after_merge(tmp_path):
    """The controller stamps its epoch's trace id into the job JSON; the
    worker's ``fleet.job`` span must adopt it (bypassing sampling), and
    the done marker must carry it back for the coordinator's merge."""
    enable_tracing(0.0)                     # sample=0: ONLY adoption keeps
    store = RecordStore.open(tmp_path / "db.jsonl")
    coord = Coordinator(tmp_path / "fleet", store)
    tid = new_trace_id()
    job = FleetJob(space="gemm", inputs=gemm_input(256, 64, 512),
                   source="retune", trace_id=tid)
    assert coord.publish([job]) == 1
    # the bus round-trips the id through JSON (unknown-field-tolerant)
    w = Worker(tmp_path / "fleet", worker_id="w1",
               tuners={"gemm": _StubTuner()})
    assert w.run_one() is True
    merged = coord.poll()
    assert merged["merged_now"] >= 1
    tr = get_tracer()
    jobs = [s for s in tr.spans() if s.name == "fleet.job"]
    assert len(jobs) == 1
    assert jobs[0].trace_id == tid          # linked across the bus
    assert jobs[0].attrs["outcome"] == "tuned"
    assert jobs[0].attrs["job"] == job.job_id
    # the done marker carries the id too (debuggability of the bus state)
    done = list((tmp_path / "fleet" / "done").glob("*.json"))
    assert any(json.loads(p.read_text()).get("trace_id") == tid
               for p in done)


def test_fleet_job_json_roundtrip_keeps_trace_id():
    job = FleetJob(space="gemm", inputs=gemm_input(128, 64, 256),
                   trace_id="abc123")
    back = FleetJob.from_json(job.to_json())
    assert back.trace_id == "abc123"
    # and a pre-PR-9 job document (no trace_id field) still loads
    d = json.loads(job.to_json())
    d.pop("trace_id")
    assert FleetJob.from_json(json.dumps(d)).trace_id == ""


def test_worker_trace_export_dumps_to_bus(tmp_path):
    enable_tracing(0.0)
    store = RecordStore.open(tmp_path / "db.jsonl")
    coord = Coordinator(tmp_path / "fleet", store)
    tid = new_trace_id()
    coord.publish([FleetJob(space="gemm", inputs=gemm_input(256, 64, 512),
                            source="retune", trace_id=tid)])
    w = Worker(tmp_path / "fleet", worker_id="wX",
               tuners={"gemm": _StubTuner()}, poll_s=0.01,
               trace_export=True)          # the `fleet worker` CLI mode
    w.run(idle_timeout_s=0.3)
    spans = collect_fleet_spans(tmp_path / "fleet")
    assert any(s.name == "fleet.job" and s.trace_id == tid for s in spans)


# ---------------------------------------------------------------------------
# serving measurer + deferred measurement queue
# ---------------------------------------------------------------------------

def test_wallclock_off_hardware_raises():
    """Wall clock times compiled kernels on a TPU; off one it refuses to
    exist rather than hand back simulated numbers under its name."""
    assert jax.default_backend() != "tpu"
    with pytest.raises(RuntimeError, match="on a TPU"):
        ServingMeasurer("wallclock")
    with pytest.raises(RuntimeError, match="on a TPU"):
        WallClockBackend().measure("gemm", dict(CFG),
                                   gemm_input(256, 64, 512))
    text = get_registry().render_prometheus()
    assert 'tunedb_measurements_total{backend="wallclock"}' not in text


def test_measurer_records_always_kept_span():
    enable_tracing(0.0)                     # even at sample=0...
    m = ServingMeasurer("sim")
    m("gemm", dict(CFG), gemm_input(256, 64, 512))
    spans = get_tracer().spans()
    assert [s.name for s in spans] == ["measure.sim"]
    assert spans[0].attrs["backend"] == "sim"
    assert spans[0].attrs["tflops"] > 0.0


def test_measure_queue_commits_winner_to_models_and_dedupes():
    q = MeasureQueue(maxlen=4)
    inputs = gemm_input(512, 64, 1024)
    cands = [dict(CFG, bm=32), dict(CFG, bm=64)]
    assert q.push("gemm", "bk", inputs, cands)
    assert not q.push("gemm", "bk", inputs, cands)      # deduped
    applied = []

    class _Models:
        def apply_measurement(self, space, backend, inp, cfg, tflops):
            applied.append((space, backend, dict(inp), dict(cfg), tflops))

    m = ServingMeasurer("sim")
    assert q.process(m, models=_Models(), max_items=2) == 1
    assert len(q) == 0 and q.processed == 1
    [(space, backend, inp, cfg, tflops)] = applied
    assert space == "gemm" and backend == "bk" and tflops > 0.0
    assert cfg in cands                     # measured winner, not a mutant
    # the shape may be re-queued after processing (memo now covers it,
    # but the queue itself must not block a future push)
    assert q.push("gemm", "bk", inputs, cands)


# ---------------------------------------------------------------------------
# the engine's tick phases, admission stamps and request stamps
# ---------------------------------------------------------------------------

PHASES = ["engine.tick.launch", "engine.tick.wait", "engine.tick.fetch",
          "engine.tick.sample", "engine.tick.retire"]


def _traced_run(small_model, lengths=(6, 7, 6), max_new=5, slots=2):
    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(max_len=64, slots=slots,
                                          trace_sample=1.0))
    rng = np.random.default_rng(0)
    eng.generate([rng.integers(0, 64, n) for n in lengths], max_new=max_new)
    return eng, eng.tracer.spans()


def test_each_tick_has_its_five_phases_in_order(small_model):
    eng, spans = _traced_run(small_model)
    ticks = [s for s in spans if s.name == "engine.tick"]
    assert len(ticks) == eng.ticks > 2
    children = {}
    for s in spans:
        if s.name.startswith("engine.tick."):
            children.setdefault(s.parent_id, []).append(s)
    for t in ticks:
        assert 1 <= t.attrs["active"] <= 2
        kids = sorted(children[t.span_id], key=lambda s: s.t0)
        assert [k.name for k in kids] == PHASES
        assert all(k.trace_id == t.trace_id for k in kids)
        # nested in the tick, one after another; the retire loop inside
        assert t.t0 <= kids[0].t0
        assert kids[-1].t0 + kids[-1].dur <= t.t0 + t.dur
        for a, b in zip(kids, kids[1:]):
            assert a.t0 + a.dur <= b.t0
    # the retire loop runs inside its tick: every token a tick appended,
    # and every retirement, is stamped within that tick's span
    inside = [(t.t0, t.t0 + t.dur) for t in ticks]
    for r in eng.last_requests:
        for stamp in (*r.token_times[1:], r.done_at):
            assert any(a <= stamp <= b for a, b in inside)


def test_admission_carries_queue_wait_and_ttft(small_model):
    _eng, spans = _traced_run(small_model, lengths=(6, 7, 6, 8))
    admits = [s for s in spans if s.name == "engine.admit"]
    assert len(admits) == 4
    for a in admits:
        assert a.attrs["queue_wait_s"] >= 0.0
        assert a.attrs["ttft_s"] >= a.attrs["queue_wait_s"]
    # two slots: the later two waited for the first two to retire
    assert sorted(a.attrs["queue_wait_s"] for a in admits)[-1] > 0.0


def test_prefill_marks_the_admission_that_compiled(small_model):
    _eng, spans = _traced_run(small_model, lengths=(6, 7, 6, 7, 9))
    seen = [(s.attrs["prompt_len"], s.attrs["compiled"]) for s in spans
            if s.name == "engine.prefill"]
    assert seen == [(6, True), (7, True), (6, False), (7, False), (9, True)]


@pytest.mark.parametrize("trace_sample", [0.0, 1.0])
def test_request_stamps_are_ordered_on_the_tracer_clock(small_model,
                                                        trace_sample):
    import time
    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(max_len=64, slots=2,
                                          trace_sample=trace_sample))
    rng = np.random.default_rng(1)
    t0 = time.perf_counter()
    outs = eng.generate([rng.integers(0, 64, n) for n in (6, 5, 7)],
                        max_new=4)
    t1 = time.perf_counter()
    assert [r.out for r in eng.last_requests] == outs
    for r in eng.last_requests:
        stamps = [r.arrived_at, r.admitted_at, *r.token_times, r.done_at]
        assert stamps == sorted(stamps)
        assert t0 <= r.arrived_at and r.done_at <= t1
        assert len(r.token_times) == len(r.out) == 4


def test_untraced_generate_calls_no_tracer_and_no_annotation(
        small_model, monkeypatch):
    from jax.profiler import TraceAnnotation

    from repro.tunedb.obs import trace as trace_mod

    cfg, params = small_model
    calls = []

    class CountingAnnotation(TraceAnnotation):
        """The annotation class the span tracer opens, counting its use
        (JAX's own annotations of compiles and transfers stay uncounted)."""

        def __init__(self, *a, **kw):
            calls.append("annotation")
            super().__init__(*a, **kw)

        @staticmethod
        def is_enabled():
            calls.append("is_enabled")
            return TraceAnnotation.is_enabled()

    monkeypatch.setattr(trace_mod, "_ANNOTATION", CountingAnnotation)
    for name in ("root", "span", "begin", "end"):
        orig = getattr(Tracer, name)

        def wrapped(*a, _orig=orig, _name=name, **kw):
            calls.append(_name)
            return _orig(*a, **kw)
        monkeypatch.setattr(Tracer, name, wrapped)
    prompts = [np.arange(6), np.arange(7), np.arange(5)]

    eng = Engine(cfg, params, ServeConfig(max_len=64, slots=2))
    eng.generate(prompts, max_new=6)
    assert eng.ticks > 0 and calls == []
    # the same counters do see a traced engine, and the annotation opens
    # only while a profiler session is active
    eng = Engine(cfg, params, ServeConfig(max_len=64, slots=2,
                                          trace_sample=1.0))
    eng.generate(prompts, max_new=6)
    assert {"root", "span", "is_enabled"} <= set(calls)
    assert "annotation" not in calls


def test_engine_spans_stand_in_the_profilers_host_plane(small_model,
                                                        tmp_path):
    import collections
    import glob

    from jax.profiler import ProfileData

    cfg, params = small_model
    eng = Engine(cfg, params, ServeConfig(max_len=64, slots=2,
                                          trace_sample=1.0))
    prompts = [np.arange(6), np.arange(7), np.arange(5)]
    eng.generate(prompts, max_new=6)            # compile outside the trace
    eng.tracer.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.generate(prompts, max_new=6)
    finally:
        jax.profiler.stop_trace()
    want = collections.Counter(s.name for s in eng.tracer.spans()
                               if s.name.startswith("engine."))
    [path] = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    got = collections.Counter(
        ev.name for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host") for line in plane.lines
        for ev in line.events if ev.name.startswith("engine."))
    assert want["engine.tick"] > 2
    assert set(PHASES) <= set(want)
    assert got == want


@pytest.fixture(scope="module")
def small_model():
    cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2, n_kv=1,
                      d_ff=64, vocab=64, dtype=jnp.float32, attn_chunk=16,
                      logit_chunk=16, remat=False)
    return cfg, init_params(cfg, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# the acceptance trace: one live Engine run, exported + parsed
# ---------------------------------------------------------------------------

def test_live_engine_trace_has_linked_spans(tmp_path, small_model):
    """ISSUE 9 acceptance: the exported Chrome trace from a live run
    contains linked spans for a router decision, a decode tick, a
    dispatch tier resolution carrying its tier, and a measurement."""
    cfg, params = small_model
    db = tmp_path / "db.jsonl"
    RecordStore.open(db).add(_rec(512, 16, 2048))
    eng = Engine(cfg, params, ServeConfig(
        max_len=48, slots=2, tunedb=str(db), router="round_robin",
        trace_sample=1.0, measure="sim"))
    assert eng.tracer is not None and eng.tracer is get_tracer()
    rng = np.random.default_rng(0)
    eng.generate([rng.integers(0, 64, 8) for _ in range(3)], max_new=8)

    out = tmp_path / "trace.json"
    n = eng.tracer.export(out)
    assert n > 0
    doc = json.loads(out.read_text())
    assert doc["otherData"]["schema"] == 1
    evs = doc["traceEvents"]
    by_name = {}
    for ev in evs:
        assert ev["ph"] == "X" and "ts" in ev and "dur" in ev
        by_name.setdefault(ev["name"], []).append(ev)

    # a router decision, linked under its admission root
    route = by_name["request.route"][0]
    assert route["args"]["policy"] == "round_robin"
    admits = {e["args"]["span_id"]: e for e in by_name["engine.admit"]}
    assert route["args"]["parent_id"] in admits
    assert (route["args"]["trace_id"]
            == admits[route["args"]["parent_id"]]["args"]["trace_id"])

    # decode ticks with their census tick number
    ticks = by_name["engine.tick"]
    assert len(ticks) >= 2 and all("tick" in e["args"] for e in ticks)

    # dispatch resolutions carry the winning tier + shape, child-linked
    # (the startup probe resolves installed shapes under its own root —
    # on TPU the decode compile emits these under the tick spans too)
    resolves = by_name["dispatch.resolve"]
    all_ids = {e["args"]["span_id"] for e in evs}
    assert all(e["args"]["tier"] in ("plan", "exact", "model", "nearest",
                                     "degraded", "tuner", "none")
               for e in resolves)
    assert all("shape" in e["args"] for e in resolves)
    assert any(e["args"]["parent_id"] in all_ids for e in resolves)

    # the §6 measurement rides the same clock (calibration guarantees one)
    measures = by_name["measure.sim"]
    assert measures[0]["args"]["backend"] == "sim"
    assert measures[0]["args"]["tflops"] > 0.0

    # prefill nests under admission in the same trace
    prefill = by_name["engine.prefill"][0]
    assert prefill["args"]["parent_id"] in admits


def test_status_snapshot_and_trace_endpoint(tmp_path):
    # disabled: schema keeps the key, route 404s
    snap = status_snapshot()
    assert snap["schema"] == 1 and snap["trace"] is None
    srv = StatusServer(port=0).start()
    try:
        with pytest.raises(urllib.error.HTTPError) as exc:
            urllib.request.urlopen(srv.url + "/trace", timeout=10)
        assert exc.value.code == 404
    finally:
        srv.stop()

    # enabled: the snapshot section and the route serve the same tracer
    tr = enable_tracing(1.0)
    with tr.root("engine.tick", tick=1):
        with tr.span("dispatch.resolve", tier="exact", space="gemm"):
            pass
    snap = status_snapshot()
    assert snap["trace"]["enabled"] is True
    assert snap["trace"]["tiers"]["exact"]["count"] == 1
    srv = StatusServer(port=0).start()
    try:
        with urllib.request.urlopen(srv.url + "/trace", timeout=10) as r:
            doc = json.loads(r.read().decode())
    finally:
        srv.stop()
    assert {e["name"] for e in doc["traceEvents"]} \
        == {"engine.tick", "dispatch.resolve"}


# ---------------------------------------------------------------------------
# CLI: tunedb trace export / summary
# ---------------------------------------------------------------------------

def _dump_spans(path):
    tr = Tracer(sample=1.0)
    with tr.root("engine.tick", tick=1):
        with tr.span("dispatch.resolve", tier="plan", space="gemm"):
            pass
    tr.export_jsonl(path)


def test_cli_trace_export_and_summary(tmp_path, capsys):
    src = tmp_path / "spans.jsonl"
    _dump_spans(src)
    out = tmp_path / "merged.json"
    assert tunedb_main(["trace", "export", "--input", str(src),
                        "--out", str(out)]) == 0
    doc = json.loads(out.read_text())
    assert len(doc["traceEvents"]) == 2
    assert "perfetto" in capsys.readouterr().out.lower()

    assert tunedb_main(["trace", "summary", "--input", str(src),
                        "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["spans"] == 2
    assert summary["tiers"]["plan"]["count"] == 1


def test_cli_trace_summary_merges_fleet_dumps(tmp_path, capsys):
    fleet = tmp_path / "fleet"
    (fleet / FLEET_TRACE_DIR).mkdir(parents=True)
    _dump_spans(fleet / FLEET_TRACE_DIR / "w1.jsonl")
    (fleet / FLEET_TRACE_DIR / "w2.jsonl").write_text('{"torn')
    assert tunedb_main(["trace", "summary", "--fleet", str(fleet),
                        "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["spans"] == 2            # torn dump skipped, not fatal
