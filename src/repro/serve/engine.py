"""Batched serving engine: slot-based continuous batching over a shared
KV/state cache.

A fixed number of decode *slots* share one jitted decode_step.  Requests are
admitted into free slots (prefill fills the slot's cache region), every
decode tick advances all active slots together at their own per-slot cache
positions, and finished requests (EOS or length budget) free their slot for
the next queued request.  This is the vLLM-style throughput recipe reduced to
its TPU-idiomatic essence: static shapes, one compiled program per
{prompt-length, decode}, per-slot bookkeeping in numpy on the host.

Prefill runs at exact prompt length (compile-cached per distinct length):
padding a prompt would poison recurrent (mamba) state and conv caches, so
exactness is correctness, not merely efficiency, for hybrid/SSM archs.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.backend import HBM_GBPS, PEAK_BF16_TFLOPS, PEAK_FP32_TFLOPS
from repro.models import (MOE_DENSE, MOE_MLP, ModelConfig, decode_step,
                          init_cache, prefill)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    max_len: int = 2048
    slots: int = 8                  # concurrent sequences
    eos_token: int = -1             # -1: never emitted (synthetic tokens)
    temperature: float = 0.0        # 0 => greedy
    seed: int = 0
    tunedb: Optional[str] = None    # warm-start: tuning-record store path
    # model artifacts dir for model-guided dispatch; None auto-discovers the
    # store's sibling `<tunedb>.models/` dir, "" disables the model tier
    tunedb_models: Optional[str] = None
    # pin dispatch lookups to one backend fingerprint (multi-backend stores);
    # None keeps the any-backend single-backend behavior
    tunedb_backend: Optional[str] = None
    # -- model-tier confidence gating (tunedb.model.ModelSet) ----------------
    # fall back to nearest-neighbor when the model's top-1 margin over top-2
    # is below this relative threshold (0 = trust every argmax) ...
    tunedb_margin: float = 0.0
    # ... or when the shape sits off the training manifold: any input
    # feature more than this many standard deviations from the featurizer's
    # training stats (0 disables the gate)
    tunedb_max_z: float = 6.0
    # -- continuous retuning (tunedb.controller.RetuneController) ------------
    retune: bool = False            # close the telemetry->tune->serve loop
    retune_interval: int = 64       # decode ticks between controller polls
    retune_drift: float = 0.25      # hot-shape mass TV distance trigger
    retune_untuned_mass: float = 0.5   # untuned fraction of window trigger
    retune_min_calls: int = 32      # window calls before a space is judged
    retune_top_k: int = 4           # novel hot shapes tuned per session
    retune_train: bool = True       # retrain + hot-swap regressors too
    # run triggered epochs on a background thread (submit-and-return polls)
    # instead of inline on the decode tick that tripped the threshold
    retune_async: bool = False
    # fleet directory to publish drift-triggered plans to (lease files for
    # external `fleet worker` processes); implies async submission
    retune_fleet: Optional[str] = None
    # cap retune epochs: engine ticks between sessions / sessions per window
    retune_cooldown_ticks: int = 0
    retune_max_sessions: int = 0    # per retune_window_s (0 = unlimited)
    retune_window_s: float = 600.0
    # skip epochs whose projected gain over the nearest-record tier is small
    retune_min_gain: float = 0.0
    # regression-sentry noise margin gating each retune's serving swap
    # (None disables the gate; see tunedb.obs.RegressionSentry)
    retune_sentry: Optional[float] = None
    # -- fleet-global telemetry + routing (tunedb.telemetry / serve.router) ---
    # > 0 (with retune_fleet set): export this engine's telemetry to the
    # fleet bus every N seconds (<fleet>/telemetry/<worker>/, cumulative
    # dumps) AND hand the retune controller the aggregated
    # FleetTelemetryView — retunes then trigger off fleet-wide hot-shape
    # mass instead of this one process's window; 0 stays process-local
    telemetry_export_s: float = 0.0
    # request-router policy for a multi-replica front-end: "affinity"
    # (route each request to the replica whose plan covers its shapes),
    # "round_robin" / "random" baselines; None disables routing.  The
    # engine registers itself as the first replica; peers are added via
    # engine.router.add_replica
    router: Optional[str] = None
    # -- golden plan artifacts (tunedb.plans; see docs/PLANS.md) --------------
    # load a persisted plan artifact directory at startup instead of
    # compiling one — the cold-start path that skips install-time model
    # scans entirely; a torn/unverifiable artifact warns and degrades to a
    # normal install-time compile
    plan_dir: Optional[str] = None
    # plan registry directory to FOLLOW: a PlanFollower daemon thread polls
    # it and atomically hot-swaps each newly published generation into this
    # engine's serving state (never a torn or stale-generation plan)
    follow: Optional[str] = None
    follow_interval_s: float = 2.0  # seconds between registry polls
    # sentry noise margin for the follower's plan-coverage diff before a
    # swap (None disables that refusal gate)
    follow_sentry: Optional[float] = 0.10
    # plan registry the retune controller publishes each successful swap's
    # compiled plan to — the coordinator half of the follow protocol
    retune_publish: Optional[str] = None
    # -- graceful degradation (docs/ROBUSTNESS.md) ----------------------------
    # per-request wall-clock deadline, enforced at decode-tick boundaries:
    # a request older than this retires with whatever tokens it has (active
    # slots) or is rejected unserved (still pending).  None disables.
    request_deadline_s: Optional[float] = None
    # admission backlog cap: while active + pending exceeds this, the
    # NEWEST pending requests are shed (rejected unserved, counted in
    # tunedb_requests_shed_total, /healthz answers 503).  None disables.
    shed_threshold: Optional[int] = None
    # -- admission policy -----------------------------------------------------
    # "fifo": admit pending requests in arrival order (the PR 1-4 behavior).
    # "store": store-aware admission — prefer requests whose prompt-length
    # prefill kernel shapes hit the frozen dispatch plan / tuned records,
    # and group equal lengths so compiled programs and plan entries are
    # reused back-to-back (every queued request is still served; only the
    # admission ORDER changes, never correctness)
    admission: str = "fifo"
    # -- observability (tunedb.obs) -------------------------------------------
    # run a StatusServer (/metrics, /status, /plan) inside this engine on
    # the given port; 0 binds an ephemeral port (Engine.status_server.port
    # says which), None disables the endpoint
    status_port: Optional[int] = None
    # -- tracing + wall-clock measurement (tunedb.obs.trace / tunedb.measure) -
    # fraction of trace roots (decode ticks, admissions) sampled into the
    # span tracer; 0 disables tracing entirely — the hot paths then make
    # zero instrument calls (E18).  Exported Chrome trace JSON loads in
    # Perfetto; see docs/OBSERVABILITY.md
    trace_sample: float = 0.0
    # §6 re-measurement backend for the model tier's top-k candidates:
    # "wallclock" times the compiled kernels and needs a TPU (the engine
    # refuses to start without one), "sim" uses the analytic simulator, None
    # disables serving-path measurement.  Measurements are scheduled into
    # idle decode gaps (MeasureQueue), never inline on dispatch
    measure: Optional[str] = None


def _ceil_div(x: int, t: int) -> int:
    return -(-x // t)


def _roofline_time_s(space: str, cfg: Mapping[str, int],
                     inputs: Mapping[str, int]) -> Optional[float]:
    """``max(compute, HBM)`` time estimate for ``cfg`` at ``inputs``.

    A two-term roofline from the ``core.backend`` chip constants — peak
    MXU TFLOPS for the dtype against HBM bandwidth — with the *block
    schedule* charged the way the simulator charges it: compute covers the
    ceil-padded grid (``gm*bm x gn*bn x gk*bk``), and A/B traffic counts
    full blocks per grid step, so quantization waste inflates BOTH axes
    while the exact-size output write pads neither.  Secondary effects
    (MXU occupancy, DMA latency, launch overhead) cancel in the ratios the
    admission floor takes, so they are deliberately left out.  Returns
    ``None`` for spaces without a roofline model.
    """
    bits = int(inputs.get("dtype_bits", 16))
    bpe = max(bits // 8, 1)
    peak = (PEAK_BF16_TFLOPS if bits <= 16 else PEAK_FP32_TFLOPS) * 1e12
    hbm = HBM_GBPS * 1e9
    if space == "gemm":
        m, n, k = int(inputs["M"]), int(inputs["N"]), int(inputs["K"])
        bm = int(cfg.get("bm") or m)
        bn = int(cfg.get("bn") or n)
        bk = int(cfg.get("bk") or k)
        mp = _ceil_div(m, bm) * bm
        np_ = _ceil_div(n, bn) * bn
        kp = _ceil_div(k, bk) * bk
        t_c = 2.0 * mp * np_ * kp / peak
        a_bytes = _ceil_div(n, bn) * mp * kp * bpe      # A slab per N step
        b_bytes = _ceil_div(m, bm) * kp * np_ * bpe     # B slab per M step
        out_bytes = m * n * bpe
        t_m = (a_bytes + b_bytes + out_bytes) / hbm
        return max(t_c, t_m)
    if space == "attention":
        b = int(inputs.get("B", 1))
        hq = int(inputs.get("Hq", 1))
        hkv = int(inputs.get("Hkv", hq))
        lq, lkv = int(inputs["Lq"]), int(inputs["Lkv"])
        d = int(inputs.get("D", 64))
        frac = 0.5 if inputs.get("causal") else 1.0
        bq = int(cfg.get("b_q") or lq)
        bkv = int(cfg.get("b_kv") or lkv)
        lqp = _ceil_div(lq, bq) * bq
        lkvp = _ceil_div(lkv, bkv) * bkv
        t_c = 4.0 * b * hq * lqp * lkvp * d * frac / peak
        qo_bytes = 2 * b * hq * lq * d * bpe            # Q read + O write
        kv_bytes = 2 * b * hkv * lkv * d * bpe
        t_m = (qo_bytes + kv_bytes) / hbm
        return max(t_c, t_m)
    return None


def _useful_flops(space: str, inputs: Mapping[str, int]) -> Optional[float]:
    if space == "gemm":
        return 2.0 * inputs["M"] * inputs["N"] * inputs["K"]
    if space == "attention":
        frac = 0.5 if inputs.get("causal") else 1.0
        return (4.0 * inputs.get("B", 1) * inputs.get("Hq", 1)
                * inputs["Lq"] * inputs["Lkv"] * inputs.get("D", 64) * frac)
    return None


def _roofline_floor(space: str, near, inputs: Mapping[str, int]) -> float:
    """Projected TFLOPS of the nearest record's config at THIS shape.

    Anchored on the record's measured number: the analytic roofline only
    supplies the *ratio* between the config's throughput at the query
    shape and at the record's own shape, so chip-constant errors and every
    shape-independent effect divide out.  Falls back to the raw recorded
    TFLOPS (no penalty, the conservative choice) when the space has no
    roofline model.
    """
    t_q = _roofline_time_s(space, near.config, inputs)
    t_r = _roofline_time_s(space, near.config, near.inputs)
    u_q = _useful_flops(space, inputs)
    u_r = _useful_flops(space, near.inputs)
    if not t_q or not t_r or not u_q or not u_r:
        return near.tflops
    return near.tflops * (u_q / t_q) / (u_r / t_r)


def _count_admission(space: str, decision: str) -> None:
    """Padded-vs-native bucket decisions into the metrics registry."""
    try:
        from repro.tunedb.obs.metrics import get_registry
        get_registry().counter(
            "tunedb_admission_decisions_total",
            "store-aware admission bucket outcomes").inc(
                space=space, decision=decision)
    except Exception:
        pass    # observability never blocks admission


class StoreAwareAdmission:
    """Store-aware batch admission: prefer shapes the dispatch plan serves.

    Two decisions, both made from RECORDED numbers only (no measurement on
    the admission path):

    * :meth:`bucket` — for one dispatchable work shape, whether to pad its
      ``pad_dims`` up to a tuned record's shape.  Padding a GEMM's M (zero
      rows in, garbage rows sliced off) is mathematically exact, so the
      only question is throughput: the padded run delivers the record's
      measured TFLOPS scaled by the useful-work fraction, while the exact
      shape would be served by its nearest neighbor's config paying an
      analytic block-quantization penalty (``ceil(dim/block)`` waste — the
      same ``_align_eff`` structure the simulator charges).  Pad exactly
      when the recorded-TFLOPS arithmetic says the overhead beats the
      untuned config, never past ``max_pad`` relative extra work.

    * :meth:`pick` — which pending request the engine admits into a free
      slot next: prompt lengths whose captured prefill kernel shapes hit
      the frozen plan score highest, equal lengths group back-to-back
      (compiled-program and plan-entry reuse), unknown lengths sit in the
      middle (they must compile either way).  FIFO order breaks ties, and
      every request is still served — only the order changes.
    """

    def __init__(self, *, pad_dims=("M",), max_pad: float = 1.0):
        self.pad_dims = tuple(pad_dims)
        self.max_pad = max_pad
        self.padded = 0                   # bucket() decisions that padded
        self.exact = 0
        self._score_memo: Dict[tuple, float] = {}

    # -- shape bucketing ------------------------------------------------------
    def bucket(self, space: str, inputs: Mapping[str, int]
               ) -> Tuple[Dict[str, int], str]:
        """(dispatch shape, "hit"|"exact"|"padded") for one work item."""
        from repro.tunedb.store import serving_state
        state = serving_state()
        store = state.store
        if store is None:
            return dict(inputs), "exact"
        fp = state.fingerprint
        if store.contains(space, inputs, backend=fp):
            _count_admission(space, "hit")
            return dict(inputs), "hit"    # already tuned: nothing to decide
        # the untuned floor: what the nearest-neighbor tier would deliver —
        # its recorded TFLOPS rescaled by the compute/bandwidth roofline
        # ratio between this shape and the record's own (see
        # ``_roofline_floor``).  The record's measured number anchors the
        # estimate; the roofline only says how much MORE (or less) block
        # quantization its config pays here, on whichever axis — MXU peak
        # or HBM bandwidth — actually bounds the kernel.  This replaces the
        # blanket ``rel ** 0.5`` damping of PR 5, which split the regimes
        # by fiat instead of deriving the boundedness from chip constants.
        floor = 0.0
        near = store.nearest(space, inputs, backend=fp, count=False)
        if near is not None:
            floor = _roofline_floor(space, near, inputs)
        best_rec, best_eff = None, floor
        # candidates come from the store's comparable-shape group (same
        # dim names + exact-match values), not a full-store scan — the
        # cost per decision tracks the group size, not the index size
        for rec in store.neighbors(space, inputs):
            if fp is not None and rec.backend != fp:
                continue
            work, ok = 1.0, True
            for k, v in inputs.items():
                rv = rec.inputs[k]
                if k in self.pad_dims:
                    if rv < v:
                        ok = False
                        break
                    work *= v / rv
                elif rv != v:
                    ok = False
                    break
            # work is the useful fraction; 1/work - 1 is the pad overhead
            if not ok or work * (1.0 + self.max_pad) < 1.0:
                continue
            eff = rec.tflops * work       # recorded TFLOPS, usefully spent
            if eff > best_eff:
                best_rec, best_eff = rec, eff
        if best_rec is None:
            self.exact += 1
            _count_admission(space, "exact")
            return dict(inputs), "exact"
        self.padded += 1
        _count_admission(space, "padded")
        return dict(best_rec.inputs), "padded"

    # -- engine admission order -----------------------------------------------
    def _length_score(self, n: int, prefill_shapes: Mapping[int, list],
                      state) -> float:
        shapes = prefill_shapes.get(n)
        if not shapes:
            return 0.5                    # unknown length: must compile anyway
        memo_key = (state.generation, n)
        score = self._score_memo.get(memo_key)
        if score is not None:
            return score
        from repro.tunedb.store import shape_key
        hits = 0
        for space, inputs in shapes:
            entry = (state.plan.lookup(space, shape_key(inputs))
                     if state.plan is not None else None)
            if entry is not None or (
                    state.store is not None
                    and state.store.contains(space, inputs,
                                             backend=state.fingerprint)):
                hits += 1
        score = hits / len(shapes)
        if len(self._score_memo) > 1024:
            self._score_memo.clear()
        self._score_memo[memo_key] = score
        return score

    def pick(self, pending: list, prefill_shapes: Mapping[int, list],
             last_len: Optional[int] = None) -> int:
        """Index into ``pending`` of the request to admit next."""
        from repro.tunedb.store import serving_state
        state = serving_state()
        best_i, best_score = 0, -1.0
        for i, req in enumerate(pending):
            n = len(req.prompt)
            score = self._length_score(n, prefill_shapes, state)
            if last_len is not None and n == last_len:
                score += 0.25             # program + plan-entry reuse
            if score > best_score + 1e-9:  # stable: FIFO breaks ties
                best_i, best_score = i, score
        return best_i


# shared reusable no-op context: the untraced engine loop enters this one
# module-level object instead of allocating per tick
_NULL_CTX = contextlib.nullcontext()


def _span(tr, name: str, **attrs):
    """Child span ``name`` under the open root, or the shared no-op when
    tracing is off (``tr`` None: no Tracer call at all)."""
    return _NULL_CTX if tr is None else tr.span(name, **attrs)


def _expert_attrs(span, rows) -> None:
    """A sampled span's routing counters, from the step's rows per expert
    (layers, E): ``moe_rows`` (routed rows over all layers),
    ``experts_touched`` (mean over layers of experts given a row) and
    ``expert_rows_max`` (the largest group).  Fetched only here, so an
    unsampled step never waits for them."""
    if span is None or rows is None:
        return
    r = np.asarray(rows)
    span.set(moe_rows=int(r.sum()),
             experts_touched=float((r > 0).sum(-1).mean()),
             expert_rows_max=int(r.max()))


@dataclasses.dataclass
class Request:
    """One request and its stamps, all on ``time.perf_counter`` (the span
    tracer's clock), always recorded whether or not tracing is on."""

    prompt: np.ndarray              # (len,) int32
    max_new: int
    out: List[int] = dataclasses.field(default_factory=list)
    arrived_at: float = 0.0         # entry into the admission queue
    admitted_at: Optional[float] = None   # admission into a slot began
    # when each token of ``out`` reached the host, one stamp per token
    token_times: List[float] = dataclasses.field(default_factory=list)
    done_at: Optional[float] = None  # retired, shed or rejected
    shed: bool = False              # rejected unserved by load shedding
    deadline_exceeded: bool = False  # cut short / rejected by the deadline


class Engine:
    def __init__(self, cfg: ModelConfig, params: Any, serve_cfg: ServeConfig,
                 *, retune_tuners: Optional[Dict[str, Any]] = None):
        self.cfg, self.params, self.sc = cfg, params, serve_cfg
        # end-to-end tracing: install (or retune the sampling of) the
        # process-global span tracer BEFORE anything below runs, so install
        # paths, calibration measurements, and the first prefill all land
        # in the same trace stream.  trace_sample=0 leaves tracing exactly
        # as it was — usually disabled, costing zero instrument calls.
        self.tracer = None
        if serve_cfg.trace_sample > 0:
            from repro.tunedb.obs.trace import enable_tracing
            self.tracer = enable_tracing(serve_cfg.trace_sample)
        # Warm start (tunedb): install the record store + model artifacts so
        # kernel dispatch resolves tuned configs from day-one traffic without
        # any tuner (or its training cost) in the serving process.  Like
        # install_tuner, both are PROCESS-GLOBAL dispatch state: a later
        # Engine with a tunedb path retargets them, tunedb=None leaves them
        # untouched, and repro.tunedb.clear_store()/clear_models()
        # uninstalls.  A missing or fully-torn store file and unreadable
        # model artifacts DEGRADE (warn once, heuristics tier keeps serving)
        # instead of failing the engine.
        self.tunedb_store = None
        self.tunedb_models = None
        self._models_dir = None
        if serve_cfg.tunedb or serve_cfg.tunedb_models or serve_cfg.plan_dir:
            import pathlib
            import warnings

            from repro.tunedb.model import (ModelSet, default_models_dir,
                                            install_models)
            models_dir = serve_cfg.tunedb_models
            if serve_cfg.tunedb:
                from repro.tunedb import RecordStore, install_store
                store_path = pathlib.Path(serve_cfg.tunedb)
                if not store_path.exists():
                    warnings.warn(
                        f"tunedb store {store_path} does not exist; serving "
                        "starts with an empty store (heuristics fallback)",
                        RuntimeWarning, stacklevel=2)
                self.tunedb_store = RecordStore.open(store_path)
                if self.tunedb_store.n_skipped \
                        and not self.tunedb_store.n_lines:
                    warnings.warn(
                        f"tunedb store {store_path} is torn beyond the tail "
                        f"({self.tunedb_store.n_skipped} unreadable lines, 0 "
                        "records); serving degrades to heuristics",
                        RuntimeWarning, stacklevel=2)
                if serve_cfg.plan_dir is None:
                    install_store(self.tunedb_store,
                                  fingerprint=serve_cfg.tunedb_backend)
                if models_dir is None:       # auto-discover next to the store
                    models_dir = default_models_dir(store_path)
            elif serve_cfg.plan_dir is None:
                # models-only config: no store install runs, but the explicit
                # backend pin must still take effect — otherwise the model
                # tier serves the newest any-backend regressor (or a prior
                # engine's stale pin) despite `tunedb_backend`
                from repro.tunedb.store import install_serving
                install_serving(fingerprint=serve_cfg.tunedb_backend)
            models = ModelSet.load(models_dir) if models_dir else ModelSet()
            # serving policy lives on the ModelSet: confidence gating keeps a
            # confidently-wrong regressor from undercutting a nearby record
            models.margin_threshold = serve_cfg.tunedb_margin
            models.max_feature_z = serve_cfg.tunedb_max_z
            if len(models) or models.skipped:
                self.tunedb_models = models
            self._models_dir = models_dir or None
            if serve_cfg.plan_dir is not None:
                # golden cold start (docs/PLANS.md): ONE install carrying
                # store + models + the persisted plan, so no install-time
                # plan compile — and none of its model scans — ever runs;
                # a rejected artifact degrades to the normal compile
                from repro.tunedb.plans import (PlanArtifactError,
                                                check_freshness, load_plan,
                                                read_manifest)
                from repro.tunedb.store import install_serving
                plan = None
                try:
                    plan = load_plan(serve_cfg.plan_dir)
                    note = check_freshness(read_manifest(serve_cfg.plan_dir),
                                           self.tunedb_store)
                    if note:
                        warnings.warn(
                            f"plan artifact {serve_cfg.plan_dir}: {note}",
                            RuntimeWarning, stacklevel=2)
                except PlanArtifactError as e:
                    warnings.warn(
                        f"plan artifact {serve_cfg.plan_dir} rejected ({e}); "
                        "compiling a plan from the store instead",
                        RuntimeWarning, stacklevel=2)
                install_serving(store=self.tunedb_store,
                                models=models if len(models) else None,
                                fingerprint=serve_cfg.tunedb_backend,
                                plan=plan)
            else:
                # retarget the global model tier to THIS config's artifacts —
                # including installing None when there are none (or the tier
                # is disabled with tunedb_models="") so a previous Engine's
                # regressors never serve another store's traffic
                install_models(models if len(models) else None)
        # wall-clock measurer (paper §6 re-measurement, on the real clock):
        # the model tier's top-k candidates are re-measured by
        # ServingMeasurer — the compiled kernel's wall clock on a TPU, or
        # the simulator when measure="sim" — but never inline: predict()
        # enqueues onto the MeasureQueue and the controller poll drains it
        # in idle decode gaps (see maybe_retune).  One tiny calibration
        # GEMM runs now and proves the backend path before traffic
        # arrives: if it fails, the engine does not start.
        self.measurer = None
        self._measure_queue = None
        if serve_cfg.measure:
            from repro.core.space import gemm_input
            from repro.tunedb.measure import MeasureQueue, ServingMeasurer
            from repro.tunedb.store import serving_state
            self.measurer = ServingMeasurer(serve_cfg.measure)
            self._measure_queue = MeasureQueue()
            live_models = serving_state().models
            if live_models is not None:
                live_models.measurer = self.measurer
                live_models.measure_queue = self._measure_queue
            self.measurer("gemm",
                          {"bm": 128, "bn": 128, "bk": 128,
                           "k_unroll": 1, "k_split": 1, "order": 0,
                           "acc32": 1, "prefetch": 2},
                          gemm_input(256, 256, 256, 16))
        # startup dispatch probe: resolve each installed shape once through
        # the real dispatch path so the trace (and tier_latency) carries
        # tier attribution immediately — on TPU the decode compile would do
        # this anyway, but a CPU dev box's model path never enters the
        # Pallas kernels, and its /trace view should still show which tier
        # each tuned shape would serve from.
        if self.tracer is not None and (serve_cfg.tunedb
                                        or serve_cfg.plan_dir):
            self._probe_dispatch()
        self.cache = init_cache(cfg, serve_cfg.slots, serve_cfg.max_len)
        self.lengths = np.zeros(serve_cfg.slots, np.int64)
        self.slot_req: List[Optional[Request]] = [None] * serve_cfg.slots
        self._rng = jax.random.PRNGKey(serve_cfg.seed)
        self.ticks = 0

        # a sparse-expert model's steps also return the rows routed to each
        # expert, (layers, E), which a sampled span reads
        self._moe = any(m in (MOE_MLP, MOE_DENSE) for _, m in cfg.pattern)
        moe = self._moe
        self._decode = jax.jit(
            lambda p, t, c, i: decode_step(p, cfg, t, c, i,
                                           expert_rows=moe))
        self._prefill_fns: Dict[int, Callable] = {}
        # jit tick telemetry: dispatch records at TRACE time only, so the
        # engine captures which kernel shapes each compiled program executes
        # and replays them per tick — true frequencies, not a compile census
        self._decode_shapes: Optional[List] = None
        self._prefill_shapes: Dict[int, List] = {}
        # the requests of the latest generate call, with their stamps
        self.last_requests: List[Request] = []
        # store-aware admission: reorder/group pending requests toward
        # plan-hit prefill shapes ("fifo" keeps arrival order)
        self.admission = (StoreAwareAdmission()
                          if serve_cfg.admission == "store" else None)
        self._last_admit_len: Optional[int] = None
        # graceful degradation counters (request_deadline_s/shed_threshold):
        # shedding flips while the backlog is over the cap and feeds the
        # /healthz probe, so balancers stop routing to a drowning replica
        self.shed_requests = 0
        self.deadline_retired = 0
        self.shedding = False
        # fleet-global telemetry: export this engine's counters to the bus
        # and aggregate every replica's dumps into one global view the
        # retune controller reads (drift/untuned-mass off FLEET-wide
        # traffic, not this process's window) — own dumps are excluded
        # from the aggregate so local counts never fold in twice
        self.exporter = None
        self._fleet_view = None
        if serve_cfg.retune_fleet and serve_cfg.telemetry_export_s > 0:
            from repro.tunedb.fleet import FleetDir
            from repro.tunedb.telemetry import (FleetTelemetryView,
                                                TelemetryExporter,
                                                get_telemetry)
            tel_dir = FleetDir(serve_cfg.retune_fleet).telemetry_dir()
            self.exporter = TelemetryExporter(
                get_telemetry(), tel_dir,
                interval_s=serve_cfg.telemetry_export_s).start()
            self._fleet_view = FleetTelemetryView(
                tel_dir, exclude={self.exporter.worker_id},
                refresh_s=serve_cfg.telemetry_export_s)
        self.controller = None
        self._next_retune_tick = 0
        if serve_cfg.retune or serve_cfg.retune_fleet:
            self._init_controller(retune_tuners)
        # shape-affinity request router: this engine registers itself as
        # the first routable replica (its live plan + active-slot load);
        # front-ends add peer replicas through engine.router.add_replica
        self.router = None
        if serve_cfg.router:
            from repro.tunedb.store import serving_state
            from .router import make_router
            self.router = make_router(serve_cfg.router)
            self.router.add_replica(
                "local",
                plan=lambda: serving_state().plan,
                load=lambda: sum(r is not None for r in self.slot_req))
        # plan follower: a daemon thread adopting golden plan generations a
        # coordinator publishes to the registry — each one digest-verified,
        # sentry-diffed, and swapped in atomically (docs/PLANS.md)
        self.follower = None
        if serve_cfg.follow:
            from repro.tunedb.plans import PlanFollower
            follow_sentry = None
            if serve_cfg.follow_sentry is not None:
                from repro.tunedb.obs import RegressionSentry
                follow_sentry = RegressionSentry(
                    noise_margin=serve_cfg.follow_sentry)
            self.follower = PlanFollower(
                serve_cfg.follow, store=self.tunedb_store,
                fingerprint=serve_cfg.tunedb_backend,
                poll_s=serve_cfg.follow_interval_s,
                sentry=follow_sentry).start()
        # in-process observability endpoint: /metrics, /status, /plan read
        # the live serving state this engine just installed (plus its
        # controller's retune history and fleet bus, when configured)
        self.status_server = None
        if serve_cfg.status_port is not None:
            from repro.tunedb.obs import StatusServer
            self.status_server = StatusServer(
                port=serve_cfg.status_port,
                controller=self.controller,
                fleet=serve_cfg.retune_fleet,
                follower=self.follower,
                router=self.router,
                tracer=self.tracer,
                health=self._health).start()

    def _health(self):
        """/healthz readiness: 503 while this replica is shedding load."""
        if self.shedding:
            return (False, "shedding load: admission backlog over "
                           "shed_threshold")
        return True

    @staticmethod
    def _count_degraded(kind: str, n: int = 1) -> None:
        try:
            from repro.tunedb.obs.metrics import get_registry
            reg = get_registry()
            if kind == "shed":
                reg.counter(
                    "tunedb_requests_shed_total",
                    "requests rejected unserved by admission load shedding",
                ).inc(n)
            else:
                reg.counter(
                    "tunedb_request_deadline_exceeded_total",
                    "requests cut short or rejected by request_deadline_s",
                ).inc(n, state=kind)
        except Exception:       # metrics must never break serving
            pass

    def _probe_dispatch(self, max_shapes: int = 8) -> None:
        """Resolve a few installed shapes through kernel dispatch under a
        ``dispatch.probe`` trace root (always kept — one per engine start).
        Purely observational: configs are resolved and discarded."""
        try:
            from repro.kernels.dispatch import _tuned_cfg
            from repro.tunedb.obs.trace import new_trace_id
            from repro.tunedb.store import serving_state
            store = serving_state().store
            if store is None:
                return
            seen = set()
            with self.tracer.root("dispatch.probe",
                                  trace_id=new_trace_id()):
                for rec in store.records():
                    key = (rec.space, tuple(sorted(rec.inputs.items())))
                    if key in seen:
                        continue
                    seen.add(key)
                    _tuned_cfg(rec.space, rec.inputs)
                    if len(seen) >= max_shapes:
                        break
        except Exception:
            pass                # a probe must never stop serving

    def _init_controller(self, retune_tuners: Optional[Dict[str, Any]]) -> None:
        """Close the loop in-process: drift-triggered sessions + hot-swap.

        Uses the warm-start store when one was configured; otherwise installs
        a fresh in-memory store so session results have somewhere to land
        (and exact-tier dispatch picks them up immediately)."""
        from repro.tunedb import RecordStore, install_store
        from repro.tunedb.controller import RetuneConfig, RetuneController
        from repro.tunedb.store import get_store
        sc = self.sc
        store = self.tunedb_store or get_store()
        if store is None:
            store = RecordStore()
            install_store(store, fingerprint=sc.tunedb_backend)
            self.tunedb_store = store
        self.controller = RetuneController(
            store,
            # the aggregated fleet view when telemetry export is on: drift
            # and untuned-mass judge GLOBAL hot-shape mass, so a shape no
            # single replica's window would trip on still triggers here
            telemetry=self._fleet_view,
            tuners=retune_tuners,
            models_dir=self._models_dir,
            async_mode=sc.retune_async,
            fleet_dir=sc.retune_fleet,
            measurer=self.measurer,
            measure_queue=self._measure_queue,
            cfg=RetuneConfig(
                drift_threshold=sc.retune_drift,
                untuned_mass_threshold=sc.retune_untuned_mass,
                min_calls=sc.retune_min_calls,
                top_k_shapes=sc.retune_top_k,
                retrain=sc.retune_train,
                cooldown_ticks=sc.retune_cooldown_ticks,
                max_sessions_per_window=sc.retune_max_sessions,
                session_window_s=sc.retune_window_s,
                min_gain=sc.retune_min_gain,
                sentry=sc.retune_sentry,
                publish=sc.retune_publish))
        self._next_retune_tick = sc.retune_interval

    def maybe_retune(self):
        """Poll the retune controller every ``retune_interval`` decode ticks.

        Returns the RetuneReport when a drift-triggered retune ran this
        tick, else None.  A no-trigger poll is a telemetry snapshot diff —
        microseconds against a multi-millisecond decode tick.  In async
        mode (``retune_async``/``retune_fleet``) a triggered poll only
        submits the epoch; the report surfaces on the first poll after the
        background session+merge+retrain completes its atomic swap.

        This is also the idle-decode-gap measurement slot: a few pending
        §6 re-measurements (MeasureQueue) drain here every tick — via the
        controller when one runs, directly otherwise — so measurements
        never sit inline on a dispatch resolution.
        """
        q = self._measure_queue
        if q is not None and len(q):
            if self.controller is not None:
                self.controller.process_measurements()
            else:
                from repro.tunedb.store import serving_state
                q.process(self.measurer, models=serving_state().models)
        if self.controller is None or self.ticks < self._next_retune_tick:
            return None
        self._next_retune_tick = self.ticks + self.sc.retune_interval
        return self.controller.maybe_retune(tick=self.ticks)

    # -- prefill ---------------------------------------------------------------
    def _prefill_one(self, slot: int, req: Request):
        """Prefill ``req`` into ``slot``; returns the rows routed to each
        expert, (layers, E) on the device, or None without experts."""
        from repro.tunedb.telemetry import get_telemetry

        cfg, sc, moe = self.cfg, self.sc, self._moe
        n = len(req.prompt)
        tokens = jnp.asarray(req.prompt[None])
        if n not in self._prefill_fns:
            def fn(params, tokens):
                single = init_cache(cfg, 1, sc.max_len)
                return prefill(params, cfg, {"tokens": tokens}, single,
                               expert_rows=moe)
            self._prefill_fns[n] = jax.jit(fn)
            # compiling call: capture the kernel shapes this prompt length
            # traces (the census count doubles as this execution's tick)
            with get_telemetry().capture() as cap:
                logits, single, *rows = self._prefill_fns[n](self.params,
                                                             tokens)
            self._prefill_shapes[n] = cap.shapes
        else:
            logits, single, *rows = self._prefill_fns[n](self.params, tokens)
            if self._prefill_shapes.get(n):
                get_telemetry().record_ticks(self._prefill_shapes[n])

        def merge(big, small):
            # big (repeats, slots, ...); small (repeats, 1, ...)
            return jax.lax.dynamic_update_index_in_dim(big, small[:, 0],
                                                       slot, 1)
        self.cache = jax.tree_util.tree_map(merge, self.cache, single)
        self.lengths[slot] = n
        self.slot_req[slot] = req
        tok = int(self._sample(np.asarray(logits)[:, : cfg.vocab])[0])
        req.out.append(tok)
        req.token_times.append(time.perf_counter())
        return rows[0] if rows else None

    def _sample(self, logits: np.ndarray) -> np.ndarray:
        if self.sc.temperature <= 0:
            return logits.argmax(-1)
        self._rng, k = jax.random.split(self._rng)
        return np.asarray(jax.random.categorical(
            k, jnp.asarray(logits) / self.sc.temperature))

    # -- main loop --------------------------------------------------------------
    def generate(self, prompts: List[np.ndarray], max_new: int = 32
                 ) -> List[List[int]]:
        """Continuous-batching loop: admit -> decode tick -> retire.

        The call's requests, with their stamps, stay readable on
        ``last_requests`` after it returns."""
        sc = self.sc
        t_arrive = time.perf_counter()
        queue = [Request(np.asarray(p, np.int32), max_new,
                         arrived_at=t_arrive) for p in prompts]
        self.last_requests = queue
        pending = list(queue)
        active = 0
        # tracing: each admission and each decode tick opens its own trace
        # root (sampled per trace_sample); router decisions, prefill, the
        # tick's phases, dispatch-tier resolutions, and idle-gap
        # measurements nest under whichever root is open on this thread.
        # tr None = the untraced path: zero instrument calls.
        tr = self.tracer

        while pending or active:
            # graceful degradation, both checks at tick/admit boundaries:
            # overdue PENDING requests are rejected unserved (their slot
            # time is already lost), and while the backlog is over
            # shed_threshold the NEWEST arrivals are shed so the oldest
            # still meet their deadlines.  A shed/expired request keeps
            # whatever tokens it has; its flags say why it stopped.
            if sc.request_deadline_s is not None and pending:
                now = time.perf_counter()
                expired = [r for r in pending
                           if now - r.arrived_at > sc.request_deadline_s]
                if expired:
                    for req in expired:
                        req.deadline_exceeded = True
                        req.done_at = now
                    pending = [r for r in pending if not r.deadline_exceeded]
                    self.deadline_retired += len(expired)
                    self._count_degraded("rejected", len(expired))
            if sc.shed_threshold is not None:
                shed_now = 0
                while active + len(pending) > sc.shed_threshold:
                    req = pending.pop()          # newest arrival goes first
                    req.shed = True
                    req.done_at = time.perf_counter()
                    shed_now += 1
                if shed_now:
                    self.shed_requests += shed_now
                    self.shedding = True
                    self._count_degraded("shed", shed_now)
                elif active + len(pending) < sc.shed_threshold:
                    self.shedding = False        # backlog drained: healthy
            while pending:                       # admit into free slots
                slot = next((i for i, r in enumerate(self.slot_req)
                             if r is None), None)
                if slot is None:
                    break
                nxt = 0
                if self.admission is not None and len(pending) > 1:
                    nxt = self.admission.pick(pending, self._prefill_shapes,
                                              last_len=self._last_admit_len)
                req = pending.pop(nxt)
                req.admitted_at = time.perf_counter()
                self._last_admit_len = len(req.prompt)
                n = len(req.prompt)
                with (tr.root("engine.admit", prompt_len=n)
                      if tr is not None else _NULL_CTX) as root:
                    if self.router is not None:
                        # single-process engine: the decision is recorded
                        # (and scraped at /status) even though the only
                        # replica is us — a front-end holding the same
                        # router object over several engines gets real
                        # placement from this call
                        self.router.route(self._prefill_shapes.get(n, []))
                    # compiled: this length's program is traced (and
                    # compiled or loaded from the compile cache) right here
                    with _span(tr, "engine.prefill", prompt_len=n,
                               compiled=n not in self._prefill_fns) as sp:
                        rows = self._prefill_one(slot, req)
                        _expert_attrs(sp, rows)
                    if root is not None:
                        root.set(
                            queue_wait_s=req.admitted_at - req.arrived_at,
                            ttft_s=req.token_times[0] - req.arrived_at)
                active += 1
            if active == 0:
                break

            with (tr.root("engine.tick", tick=self.ticks, active=active)
                  if tr is not None else _NULL_CTX) as root:
                active -= self._decode_tick(tr, root)
        return [r.out for r in queue]

    def _decode_tick(self, tr, root=None) -> int:
        """One decode tick for every slot, in five phases (each a child
        span when traced); returns how many requests it retired.  Idle
        slots run on garbage that is discarded — static shapes, zero
        recompiles.  ``root``, the sampled tick span, gets the routing
        counters of a sparse-expert model."""
        from repro.tunedb.telemetry import get_telemetry

        sc = self.sc
        with _span(tr, "engine.tick.launch"):
            last = np.array([
                (r.out[-1] if r is not None and r.out else 0)
                for r in self.slot_req], np.int32)[:, None]
            idx = jnp.asarray(self.lengths, jnp.int32)  # slot position
            if self._decode_shapes is None:
                # compiling tick: the trace-time census IS this tick's count
                with get_telemetry().capture() as cap:
                    logits, self.cache, *rows = self._decode(
                        self.params, jnp.asarray(last), self.cache, idx)
                self._decode_shapes = cap.shapes
            else:
                logits, self.cache, *rows = self._decode(
                    self.params, jnp.asarray(last), self.cache, idx)
                if self._decode_shapes:
                    get_telemetry().record_ticks(self._decode_shapes)
        with _span(tr, "engine.tick.wait"):
            logits.block_until_ready()
        with _span(tr, "engine.tick.fetch"):
            host = np.asarray(logits)
        with _span(tr, "engine.tick.sample"):
            toks = self._sample(host[:, : self.cfg.vocab])
        _expert_attrs(root, rows[0] if rows else None)
        # the tick's one clock read: every token of this tick reached the
        # host by now, and the deadline is judged against it
        now = time.perf_counter()
        self.ticks += 1
        retired = 0
        with _span(tr, "engine.tick.retire"):
            # fold this tick's lock-free telemetry rings into the counters:
            # one batched drain per tick instead of one lock per kernel call
            get_telemetry().drain_pending()
            self.maybe_retune()
            for s, req in enumerate(self.slot_req):
                if req is None:
                    continue
                self.lengths[s] += 1
                tok = int(toks[s])
                req.out.append(tok)
                req.token_times.append(now)
                overdue = (sc.request_deadline_s is not None
                           and now - req.arrived_at > sc.request_deadline_s)
                if overdue:
                    # deadline at the tick boundary: the request retires
                    # with the tokens it has instead of starving the queue
                    req.deadline_exceeded = True
                    self.deadline_retired += 1
                    self._count_degraded("retired", 1)
                if (overdue or tok == sc.eos_token
                        or len(req.out) >= req.max_new
                        or self.lengths[s] + 1 >= sc.max_len):
                    req.done_at = now
                    self.slot_req[s] = None
                    self.lengths[s] = 0
                    retired += 1
        return retired
