"""tunedb fleet: lease protocol, coordinator/worker crash recovery, async
drift-triggered retunes, and the satellite fixes that ride along.

Pins the PR-4 contracts: a lease is claimed by exactly one racer (atomic
rename); a crashed worker's lease expires and its job is re-queued with no
duplicate serving commit; a restarted coordinator resumes the shard merge
from its cursors; ``RecordStore.merge`` preserves record provenance; the
retune controller budgets epochs (cooldown / sessions-per-window /
projected-gain floor); the model tier declines low-margin and off-manifold
resolutions; and an in-engine ASYNC retune triggered under synthetic drift
hot-swaps the serving state without blocking any decode tick (tick p99
within 2% of steady state).
"""

import json
import threading
import time

import numpy as np
import pytest

from repro.core.backend import SimulatedTPUBackend
from repro.core.features import Featurizer
from repro.core.search import SearchResult, enumerate_legal
from repro.core.space import GEMM_SPACE, gemm_input
from repro.core.tuner import clear_tuners
from repro.kernels import dispatch
from repro.tunedb import (RecordStore, TuneRecord, clear_store,
                          clear_telemetry, get_telemetry, install_generation,
                          install_serving, install_store, serving_state)
from repro.tunedb.controller import RetuneConfig, RetuneController
from repro.tunedb.fleet import (Coordinator, FleetJob, Worker,
                                run_fleet_inline)
from repro.tunedb.model import ModelSet, clear_models, get_models
from repro.tunedb.obs.trace import reset_tracing
from repro.tunedb.session import TuningSession, backend_fingerprint
from repro.tunedb.__main__ import main as tunedb_main

CFG = {"bm": 64, "bn": 128, "bk": 128, "k_unroll": 1, "k_split": 1,
       "order": 0, "acc32": 1, "prefetch": 2}


@pytest.fixture(autouse=True)
def _clean_globals():
    def reset():
        clear_tuners()
        clear_store()
        clear_models()
        clear_telemetry()
        reset_tracing()
        dispatch.reset_fallback_warnings()
    reset()
    yield
    reset()


class StubTuner:
    """Deterministic, instant (or fixed-delay) tuner for fleet plumbing
    tests: the fleet is about coordination, not search quality."""

    def __init__(self, delay_s: float = 0.0, n_measured: int = 0,
                 fail: bool = False, fixed_cfg: bool = False):
        self.space = GEMM_SPACE
        self.backend = SimulatedTPUBackend(noise=0.0)
        self.delay_s = delay_s
        self.n_measured = n_measured     # extra top-k pairs -> sample records
        self.fail = fail
        # skip the pure-python legal-space enumeration (a GIL hog): the
        # timing tests need the background session to be sleep-shaped
        self.fixed_cfg = fixed_cfg
        self.calls = 0

    def search(self, inputs, remeasure=True):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        if self.fail:
            raise RuntimeError("synthetic tuner failure")
        if self.fixed_cfg:
            legal = [dict(CFG)]
        else:
            legal = enumerate_legal(self.space, inputs)
        cfg = legal[0]
        tf = float(self.backend.measure("gemm", cfg, inputs))
        measured = [(cfg, tf)]
        for extra in legal[1:1 + self.n_measured]:
            measured.append(
                (extra, float(self.backend.measure("gemm", extra, inputs))))
        return SearchResult(best=cfg, predicted_tflops=tf,
                            measured_tflops=tf, top_k=measured[:10],
                            n_candidates=len(legal), measured=measured)


def _shape(i: int):
    return gemm_input(256 * (i + 1), 64, 512)


def _fleet(tmp_path, **kw):
    store = RecordStore.open(tmp_path / "db.jsonl")
    coord = Coordinator(tmp_path / "fleet", store, **kw)
    return store, coord


# ---------------------------------------------------------------------------
# lease protocol
# ---------------------------------------------------------------------------

def test_publish_is_idempotent_across_lifecycle(tmp_path):
    _, coord = _fleet(tmp_path)
    job = FleetJob(space="gemm", inputs=_shape(0))
    assert coord.publish([job]) == 1
    assert coord.publish([job]) == 0               # queued: known
    fd = coord.fleet
    claimed = fd.claim()
    assert claimed is not None
    assert coord.publish([job]) == 0               # leased: known
    fd.complete(job, claimed[1], {"worker_id": "w"})
    assert coord.publish([job]) == 0               # done: never re-queued
    assert fd.counts() == {"queue": 0, "leases": 0, "done": 1, "failed": 0}
    # ... unless forced (the `fleet start --retune` path): the stale
    # terminal marker must not pin the shape forever
    assert coord.publish([job], force=True) == 1
    assert fd.counts() == {"queue": 1, "leases": 0, "done": 0, "failed": 0}
    assert coord.publish([job], force=True) == 0   # queued: still no dup


def test_publishing_revives_a_drained_fleet(tmp_path):
    """A directory that was drained once must serve later plans: publish
    clears the DRAIN marker, so new workers don't turn away at startup."""
    store, coord = _fleet(tmp_path)
    report = run_fleet_inline(            # run 1 ends with a DRAIN marker
        tmp_path / "fleet", store,
        [FleetJob(space="gemm", inputs=_shape(0))],
        n_workers=1, tuners={"gemm": StubTuner()})
    assert report.done == 1 and coord.fleet.draining()
    assert coord.publish([FleetJob(space="gemm", inputs=_shape(1))]) == 1
    assert not coord.fleet.draining()     # revived
    w = Worker(tmp_path / "fleet", worker_id="late",
               tuners={"gemm": StubTuner()}, poll_s=0.01)
    report2 = w.run(idle_timeout_s=0.5)   # does NOT exit before claiming
    assert report2.tuned == 1
    coord.poll()
    assert store.contains("gemm", _shape(1))


def test_stale_queue_wait_does_not_expire_a_fresh_claim(tmp_path):
    """A job that sat queued past the lease timeout must not be reclaimed
    the moment someone claims it: the claim freshens the mtime before the
    rename (which preserves mtime)."""
    _, coord = _fleet(tmp_path, lease_timeout_s=0.2)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    time.sleep(0.4)                       # queued longer than the timeout
    job, lease = coord.fleet.claim()
    assert coord.fleet.reclaim_expired(lease_timeout_s=0.2,
                                       max_attempts=3) == []
    assert coord.fleet.heartbeat(lease)   # still ours


def test_worker_started_before_the_bus_waits_then_attaches(tmp_path):
    """Workers may come up before any coordinator initialized the fleet
    dir: they idle (no crash) and bind once the manifest appears."""
    w = Worker(tmp_path / "fleet", worker_id="early",
               tuners={"gemm": StubTuner()}, poll_s=0.01)
    assert w.run_one() is None                     # no bus yet: just idle
    report = w.run(idle_timeout_s=0.05)
    assert report.claimed == 0
    store, coord = _fleet(tmp_path)                # the bus appears
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    assert w.run_one() is True
    coord.poll()
    assert store.contains("gemm", _shape(0))


def test_coordinator_refuses_mismatched_store(tmp_path):
    store, _ = _fleet(tmp_path)
    other = RecordStore.open(tmp_path / "other.jsonl")
    with pytest.raises(ValueError, match="was created for store"):
        Coordinator(tmp_path / "fleet", other)


def test_two_workers_racing_one_lease_single_winner(tmp_path):
    """The atomic-rename claim: over many rounds of two racers starting on a
    barrier, exactly one ever wins the single queued job."""
    _, coord = _fleet(tmp_path)
    fd = coord.fleet
    for i in range(20):
        job = FleetJob(space="gemm", inputs=_shape(i))
        assert coord.publish([job]) == 1
        barrier = threading.Barrier(2)
        wins = []

        def race():
            barrier.wait()
            got = fd.claim()
            if got is not None:
                wins.append(got)
        threads = [threading.Thread(target=race) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert len(wins) == 1, f"round {i}: {len(wins)} claim winners"
        fd.complete(wins[0][0], wins[0][1], {"worker_id": "racer"})


def test_heartbeat_keeps_lease_alive_expiry_requeues(tmp_path):
    _, coord = _fleet(tmp_path, lease_timeout_s=0.25)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    job, lease = coord.fleet.claim()
    time.sleep(0.15)
    assert coord.fleet.heartbeat(lease)            # refresh mtime
    time.sleep(0.15)
    # 0.3s since claim, 0.15s since the heartbeat: still alive
    assert coord.fleet.reclaim_expired(lease_timeout_s=0.25,
                                       max_attempts=3) == []
    time.sleep(0.3)                                # now it really expired
    assert coord.fleet.reclaim_expired(lease_timeout_s=0.25,
                                       max_attempts=3) == [job.job_id]
    assert not coord.fleet.heartbeat(lease)        # the zombie learns it lost
    requeued, _ = coord.fleet.claim()
    assert requeued.attempts == 1                  # the crash burned one


def test_expiry_exhausts_into_failed(tmp_path):
    _, coord = _fleet(tmp_path, lease_timeout_s=0.05, max_attempts=2)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    for _ in range(2):                             # claim, die, claim, die
        got = coord.fleet.claim()
        assert got is not None
        time.sleep(0.1)
        coord.poll()
    assert coord.fleet.counts()["failed"] == 1
    assert coord.fleet.claim() is None


# ---------------------------------------------------------------------------
# crash recovery
# ---------------------------------------------------------------------------

def test_worker_crash_requeues_without_duplicate_commits(tmp_path):
    """A worker dies mid-job: its lease expires, the job goes back to the
    queue, a healthy worker finishes it — and the parent store ends up with
    exactly ONE serving commit for the shape."""
    store, coord = _fleet(tmp_path, lease_timeout_s=0.1)
    inputs = _shape(3)
    coord.publish([FleetJob(space="gemm", inputs=inputs)])
    # worker 1 claims and dies: no heartbeat, no shard write, no marker
    assert coord.fleet.claim() is not None
    time.sleep(0.2)
    status = coord.poll()                          # expiry returns the job
    assert status["reclaimed"] != []
    w2 = Worker(tmp_path / "fleet", worker_id="w2",
                tuners={"gemm": StubTuner()}, poll_s=0.01)
    assert w2.run_one() is True
    assert w2.run_one() is None                    # queue is empty now
    coord.poll()
    assert store.contains("gemm", inputs)
    assert len(store.training_records()) == 1      # one commit, not two
    rec = store.get("gemm", inputs)
    assert rec.source == "fleet" and rec.merged_from == "w2"
    # repeated polls must not re-merge the shard (cursor holds)
    coord.poll()
    assert len(store.training_records()) == 1


def test_coordinator_restart_resumes_from_shard_state(tmp_path):
    store, coord = _fleet(tmp_path)
    jobs = [FleetJob(space="gemm", inputs=_shape(i)) for i in range(3)]
    coord.publish(jobs)
    w = Worker(tmp_path / "fleet", worker_id="w1",
               tuners={"gemm": StubTuner()}, poll_s=0.01)
    assert w.run_one() is True                     # one job done pre-crash
    coord.poll()
    assert len(store.training_records()) == 1

    # the coordinator "crashes"; a fresh one opens the same fleet dir
    coord2 = Coordinator(tmp_path / "fleet")
    assert coord2.store.path == store.path         # manifest remembers
    assert coord2.publish(jobs) == 0               # plan already in flight
    while w.run_one() is not None:
        pass
    coord2.poll()
    fresh = RecordStore.open(tmp_path / "db.jsonl")
    assert len(fresh) == 3
    # cursors survived the restart: the pre-crash record was not re-merged
    assert len(fresh.training_records()) == 3
    assert coord2.fleet.outstanding() == 0


def test_worker_job_failure_requeues_then_buries(tmp_path):
    store, coord = _fleet(tmp_path, max_attempts=2)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    bad = Worker(tmp_path / "fleet", worker_id="bad",
                 tuners={"gemm": StubTuner(fail=True)}, poll_s=0.01)
    assert bad.run_one() is False                  # attempt 1: requeued
    assert coord.fleet.counts()["queue"] == 1
    assert bad.run_one() is False                  # attempt 2: buried
    assert coord.fleet.counts()["failed"] == 1
    assert coord.outstanding() == 0
    assert len(store.training_records()) == 0


# ---------------------------------------------------------------------------
# inline fleet end-to-end + record equivalence
# ---------------------------------------------------------------------------

def test_fleet_matches_serial_session_records(tmp_path):
    """The distributed result must be indistinguishable from a serial
    session over the same plan: same serving records, same provenance-
    preserving log size."""
    shapes = [_shape(i) for i in range(6)]
    tuner = StubTuner(n_measured=3)

    serial_store = RecordStore.open(tmp_path / "serial.jsonl")
    session = TuningSession(tuner, serial_store, None, workers=1,
                            source="fleet")
    session.run(shapes=shapes)

    fleet_store = RecordStore.open(tmp_path / "db.jsonl")
    report = run_fleet_inline(
        tmp_path / "fleet", fleet_store,
        [FleetJob(space="gemm", inputs=s) for s in shapes],
        n_workers=3, tuners={"gemm": StubTuner(n_measured=3)})
    assert report.done == 6 and report.failed == 0
    assert report.merged_records == 6 and report.merged_samples == 6 * 3

    def view(store):
        return {(r.space, r.key, r.backend): (r.config, round(r.tflops, 9))
                for r in store.records()}
    assert view(fleet_store) == view(serial_store)
    assert len(fleet_store.training_records()) \
        == len(serial_store.training_records())


def test_merge_preserves_provenance(tmp_path):
    """The satellite bugfix: merging must not rewrite ``source`` (harvest
    and retune audits key on it); lineage lands in ``merged_from``."""
    src = RecordStore.open(tmp_path / "src.jsonl")
    src.add(TuneRecord(space="gemm", inputs=_shape(0), config=dict(CFG),
                       tflops=80.0, backend="bk", source="retune"))
    src.add(TuneRecord(space="gemm", inputs=_shape(0), config=dict(CFG),
                       tflops=1.0, backend="bk", source="sample"))
    dst = RecordStore()
    assert dst.merge(src) == 1                     # samples stay behind
    rec = dst.get("gemm", _shape(0))
    assert rec.source == "retune"                  # NOT rewritten to "merge"
    assert rec.merged_from == str(src.path)
    # explicit lineage label (the fleet's worker id) wins
    dst2 = RecordStore()
    dst2.merge(src, lineage="w7")
    assert dst2.get("gemm", _shape(0)).merged_from == "w7"
    # and the json round trip keeps it (old lines without it still load)
    line = rec.to_json()
    back = TuneRecord.from_json(line)
    assert back.merged_from == rec.merged_from
    assert TuneRecord.from_json(
        '{"space": "gemm", "inputs": {"M": 1}, "config": {}, '
        '"tflops": 1.0}').merged_from is None


# ---------------------------------------------------------------------------
# retune budget: cooldown, sessions-per-window, projected gain
# ---------------------------------------------------------------------------

def _drive_traffic(tel, inputs, n=40):
    for _ in range(n):
        tel.record("gemm", inputs)


def test_cooldown_ticks_blocks_back_to_back_epochs():
    store = RecordStore()
    install_store(store)
    tel = get_telemetry()
    controller = RetuneController(
        store, tuners={"gemm": StubTuner()},
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=False, cooldown_ticks=100))
    _drive_traffic(tel, _shape(0))
    assert controller.maybe_retune(tick=10) is not None
    _drive_traffic(tel, _shape(1))                 # fresh drift right away
    assert controller.maybe_retune(tick=60) is None     # inside cooldown
    report = controller.maybe_retune(tick=120)     # cooldown over
    assert report is not None and report.tuned == 1


def test_session_budget_per_window():
    store = RecordStore()
    install_store(store)
    tel = get_telemetry()
    controller = RetuneController(
        store, tuners={"gemm": StubTuner()},
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=False, max_sessions_per_window=1,
                         session_window_s=3600.0))
    _drive_traffic(tel, _shape(0))
    assert controller.maybe_retune() is not None
    _drive_traffic(tel, _shape(1))
    assert controller.maybe_retune() is None       # budget spent
    # the window rolls past: the same drift becomes actionable again
    controller._session_starts = [time.time() - 3601.0]
    assert controller.maybe_retune() is not None


class _StubPM:
    """resolve_model/predict_config stand-in for gain-projection tests."""

    def __init__(self, predicted):
        self.meta = {}
        self.predicted = predicted

    def predict_config(self, inputs, top_k=1):
        cfg = dict(CFG)
        return SearchResult(best=cfg, predicted_tflops=self.predicted,
                            measured_tflops=None, top_k=[(cfg, self.predicted)],
                            n_candidates=1)


class _StubModels:
    def __init__(self, predicted):
        self.pm = _StubPM(predicted)

    def resolve_model(self, space, backend=None):
        return self.pm


def test_min_gain_skips_low_upside_epochs():
    """An epoch whose model-projected win over the nearest record is below
    ``min_gain`` is skipped (debug log), not tuned."""
    store = RecordStore()
    near = _shape(0)
    store.add(TuneRecord(space="gemm", inputs=near, config=dict(CFG),
                         tflops=100.0, backend="bk"))
    install_serving(store=store, models=_StubModels(predicted=104.0))
    tel = get_telemetry()
    novel = gemm_input(288 * 1, 64, 512)           # a close, driftable shape
    controller = RetuneController(
        store, tuners={"gemm": StubTuner()},
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=False, min_gain=0.2))
    _drive_traffic(tel, novel)
    dec = controller.check()["gemm"]
    assert dec.projected_gain == pytest.approx(0.04)
    assert not dec.trigger                         # 4% < the 20% floor
    assert controller.maybe_retune() is None

    # a model that promises a real win clears the floor
    install_serving(models=_StubModels(predicted=150.0))
    dec = controller.check()["gemm"]
    assert dec.projected_gain == pytest.approx(0.5)
    assert dec.trigger
    assert controller.maybe_retune().tuned == 1


def test_min_gain_unprojectable_epoch_still_tunes():
    """No nearest record / no model => unbounded upside: never skipped."""
    store = RecordStore()
    install_store(store)                           # no models installed
    tel = get_telemetry()
    controller = RetuneController(
        store, tuners={"gemm": StubTuner()},
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=False, min_gain=0.5))
    _drive_traffic(tel, _shape(2))
    dec = controller.check()["gemm"]
    assert dec.trigger and dec.projected_gain is None
    assert controller.maybe_retune().tuned == 1


# ---------------------------------------------------------------------------
# model-tier confidence gating
# ---------------------------------------------------------------------------

def _fitted_featurizer(shapes):
    f = Featurizer(space=GEMM_SPACE)
    f.fit(f.raw_batch([(s, dict(CFG)) for s in shapes]))
    return f


class _GatePM:
    """A PerfModel stand-in with controllable top-2 predictions."""

    def __init__(self, featurizer, top):
        self.meta = {}
        self.featurizer = featurizer
        self.top = top

    def predict_config(self, inputs, top_k=1):
        return SearchResult(best=self.top[0][0],
                            predicted_tflops=self.top[0][1],
                            measured_tflops=None, top_k=self.top[:top_k],
                            n_candidates=len(self.top))


def _gate_models(margin, max_z, top):
    shapes = [gemm_input(256 * (i + 1), 64, 512) for i in range(4)]
    ms = ModelSet(margin_threshold=margin, max_feature_z=max_z)
    ms.models[("gemm", "bk")] = _GatePM(_fitted_featurizer(shapes), top)
    return ms


def test_margin_gate_declines_ambivalent_argmax():
    top = [(dict(CFG), 100.0), (dict(CFG, bm=128), 99.9)]
    gated = _gate_models(0.05, 0.0, top)
    assert gated.predict("gemm", _shape(1)) is None
    assert gated.gated == 1 and gated.misses == 1
    # same prediction, gate off: the argmax serves
    open_ms = _gate_models(0.0, 0.0, top)
    assert open_ms.predict("gemm", _shape(1)) == (CFG, 100.0)
    # a decisive margin passes the gate
    decisive = _gate_models(0.05, 0.0, [(dict(CFG), 100.0),
                                        (dict(CFG, bm=128), 80.0)])
    assert decisive.predict("gemm", _shape(1)) == (CFG, 100.0)
    assert decisive.gated == 0


def test_off_manifold_gate_z_score():
    top = [(dict(CFG), 100.0)]
    ms = _gate_models(0.0, 4.0, top)
    # a shape inside the training range serves
    assert ms.predict("gemm", _shape(2)) is not None
    # M six orders of magnitude off the manifold: decline, fall through
    far = gemm_input(1 << 22, 64, 512)
    assert ms.predict("gemm", far) is None
    assert ms.gated == 1
    # the decline is memoized like any other resolution
    assert ms.predict("gemm", far) is None
    assert ms.gated == 1


def test_gating_is_serving_policy_across_retrain_swap():
    ms = ModelSet(margin_threshold=0.07, max_feature_z=3.5)
    out = ms.merged_with(ModelSet())               # freshly trained defaults
    assert out.margin_threshold == 0.07
    assert out.max_feature_z == 3.5
    assert json.dumps(ms.stats())                  # gated counter serializes


def test_dispatch_falls_to_nearest_when_model_gated():
    """The three-tier contract under gating: a declined model resolution
    serves the nearest record, not the (possibly wrong) model argmax."""
    store = RecordStore()
    near_cfg = dict(CFG, bm=128)
    store.add(TuneRecord(space="gemm", inputs=gemm_input(1 << 21, 64, 512),
                         config=near_cfg, tflops=90.0, backend="bk"))
    wrong_cfg = dict(CFG, bm=8)
    ms = _gate_models(0.0, 4.0, [(wrong_cfg, 999.0)])
    install_serving(store=store, models=ms)
    probe = gemm_input(1 << 22, 64, 512)           # off the model's manifold
    cfg = dispatch._tuned_cfg("gemm", probe)
    assert cfg == near_cfg                         # tier 3 won, not the model
    assert ms.gated == 1


# ---------------------------------------------------------------------------
# async retunes: controller level
# ---------------------------------------------------------------------------

def test_async_submit_reap_cycle():
    store = RecordStore()
    install_store(store)
    tel = get_telemetry()
    controller = RetuneController(
        store, tuners={"gemm": StubTuner(delay_s=0.3)}, async_mode=True,
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=False))
    _drive_traffic(tel, _shape(0))
    gen0 = install_generation()
    assert controller.maybe_retune(tick=0) is None      # submit, not block
    assert controller.async_active()
    assert controller.maybe_retune(tick=16) is None     # in flight: skipped
    deadline = time.time() + 10
    report = None
    while report is None and time.time() < deadline:
        time.sleep(0.05)
        report = controller.maybe_retune(tick=32)       # eventually reaps
    assert report is not None and report.mode == "async"
    assert report.tuned == 1 and controller.retunes == 1
    assert install_generation() > gen0                  # the swap landed
    assert controller.maybe_retune(tick=48) is None     # reaped exactly once


def test_async_retrain_completes_store_and_model_swap():
    """The full async epoch: session samples -> regressor retrain -> ONE
    generation flip publishing store AND models together."""
    store = RecordStore()
    install_serving(store=store, models=None)
    tel = get_telemetry()
    controller = RetuneController(
        store, tuners={"gemm": StubTuner(n_measured=40)}, async_mode=True,
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, workers=1,
                         retrain=True, min_train_samples=10, train_epochs=2))
    _drive_traffic(tel, _shape(0))
    gen0 = install_generation()
    assert controller.maybe_retune() is None
    report = controller.wait_async(timeout=60)
    assert report is not None and report.tuned == 1
    fp = backend_fingerprint(SimulatedTPUBackend(noise=0.0))
    assert report.retrained == [f"gemm/{fp}"]
    assert install_generation() == gen0 + 1             # ONE atomic flip
    assert serving_state().store is store
    assert len(get_models()) == 1


def test_fleet_retune_swaps_only_after_merge(tmp_path):
    """Fleet-routed async epoch: the swap must not happen before the
    coordinator merged the worker's shard into the serving store."""
    store = RecordStore.open(tmp_path / "db.jsonl")
    install_store(store)
    tel = get_telemetry()
    fleet_dir = tmp_path / "fleet"
    controller = RetuneController(
        store, fleet_dir=fleet_dir, fleet_poll_s=0.02, fleet_timeout_s=30,
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, retrain=False))
    _drive_traffic(tel, _shape(0))
    gen0 = install_generation()
    assert controller.maybe_retune() is None
    # no worker yet: the epoch stays in flight, no swap
    deadline = time.time() + 5
    while not (fleet_dir / "manifest.json").exists() \
            and time.time() < deadline:
        time.sleep(0.02)
    time.sleep(0.2)
    assert controller.async_active() and install_generation() == gen0
    worker = Worker(fleet_dir, worker_id="w1",
                    tuners={"gemm": StubTuner()}, poll_s=0.01)
    worker.run(idle_timeout_s=1.0)
    report = controller.wait_async(timeout=30)
    assert report is not None and report.mode == "fleet"
    assert report.tuned == 1
    assert install_generation() == gen0 + 1
    rec = store.get("gemm", _shape(0))
    assert rec.source == "retune" and rec.merged_from == "w1"
    assert (fleet_dir / "report.json").exists()


def test_fleet_retune_needs_disk_backed_store():
    store = RecordStore()                          # in-memory: no shards
    install_store(store)
    tel = get_telemetry()
    controller = RetuneController(
        store, fleet_dir="/nonexistent-fleet",
        cfg=RetuneConfig(min_calls=8, top_k_shapes=1, retrain=False),
        tuners={"gemm": StubTuner()})
    _drive_traffic(tel, _shape(0))
    with pytest.warns(RuntimeWarning, match="disk-backed"):
        controller.maybe_retune()
    report = controller.wait_async(timeout=30)     # in-process fallback ran
    assert report is not None and report.tuned == 1


# ---------------------------------------------------------------------------
# the acceptance loop: in-engine async retune never stalls a decode tick
# ---------------------------------------------------------------------------

def _rolling_median(xs, w=5):
    """De-spike a tick-time series: isolated OS-scheduler/GC hiccups (which
    hit steady and in-flight windows alike) must not decide the comparison,
    while anything sustained — a tick genuinely waiting on session work —
    survives the filter."""
    xs = np.asarray(xs)
    k = w // 2
    return np.array([np.median(xs[max(0, i - k):i + k + 1])
                     for i in range(len(xs))])


def test_engine_async_retune_keeps_tick_p99_flat():
    """The acceptance loop: synthetic drift triggers an ASYNC retune
    mid-generate; the epoch — deliberately slowed to span hundreds of
    ticks — completes a hot-swap while decode ticks keep flowing.

    Two classes of assertion:
      * deterministic (every attempt): serving never pauses, exactly one
        epoch is submitted, the swap lands, and NO tick comes anywhere
        near the session length — the inline controller would block one
        tick for the full 0.8s session.
      * statistical: the p99 decode tick during the in-flight session
        stays within 2% of the steady-state p99 (rolling-median smoothed,
        GC parked).  Shared CI boxes occasionally inject >2% of ambient
        scheduler noise into one window, so this check may retry on a
        fresh engine; a real regression fails every attempt.
    """
    import gc

    import jax
    import jax.numpy as jnp

    from repro.models import ModelConfig, init_params
    from repro.serve import Engine, ServeConfig

    cfg = ModelConfig(name="t", n_layers=1, d_model=32, n_heads=2, n_kv=1,
                      d_ff=64, vocab=64, dtype=jnp.float32, attn_chunk=16,
                      logit_chunk=16, remat=False)
    params = init_params(cfg, jax.random.PRNGKey(0))

    ratios = []
    for attempt in range(3):
        clear_tuners()
        clear_store()
        clear_models()
        clear_telemetry()
        slow = StubTuner(delay_s=0.8, fixed_cfg=True)   # ticks are ~2ms: the
        engine = Engine(                                # session spans 100s
            cfg, params,                                # of ticks
            ServeConfig(max_len=2048, slots=2, retune=True,
                        retune_async=True, retune_interval=256,
                        retune_min_calls=8, retune_top_k=2,
                        retune_train=False, trace_sample=1.0,
                        retune_cooldown_ticks=100_000),  # exactly one epoch
            retune_tuners={"gemm": slow})
        controller = engine.controller
        assert controller is not None and controller.async_mode

        # warm the jit caches so compile never pollutes the timing window
        engine.generate([np.arange(4), np.arange(6)], max_new=8)
        engine.tracer.clear()
        controller.reset_baseline()
        # synthetic drift: novel hot shapes the store has never seen
        tel = get_telemetry()
        for i in range(3):
            _drive_traffic(tel, gemm_input(384 * (i + 1), 48, 768), n=80)

        gen0 = install_generation()
        gc.disable()                    # GC pauses are ambient, not retune
        try:
            outs = engine.generate([np.arange(4), np.arange(6)], max_new=900)
        finally:
            gc.enable()
        assert all(len(o) == 900 for o in outs)    # serving never stopped
        report = controller.wait_async(timeout=60)
        if report is None:                         # reaped in-loop already
            report = controller.last_report
        assert controller.async_submits == 1
        assert report is not None and report.tuned >= 1
        assert install_generation() > gen0         # the hot-swap landed
        assert len(controller.store.records()) >= 1
        assert all(r.source == "retune"
                   for r in controller.store.records())

        t_submit, t_done = controller.async_submit_t, controller.async_done_t
        assert t_submit is not None and t_done is not None
        ticks = sorted((s.t0, s.dur) for s in engine.tracer.spans()
                       if s.name == "engine.tick")
        steady = [w for t0, w in ticks[5:] if t0 + w < t_submit]
        inflight = [w for t0, w in ticks if t_submit <= t0 <= t_done]
        assert len(steady) >= 100 and len(inflight) >= 100, \
            (len(steady), len(inflight))
        # Inline execution would park the polling tick for the whole ~0.8s
        # epoch — a tick anywhere near the session length fails hard.
        # Smaller ambient scheduler stalls (tens to a couple hundred ms on
        # a shared box) go through the retry with the p99 check instead.
        assert max(inflight) < slow.delay_s

        p99_steady = float(np.percentile(_rolling_median(steady), 99))
        p99_inflight = float(np.percentile(_rolling_median(inflight), 99))
        ratios.append((p99_inflight / p99_steady, max(inflight)))
        if ratios[-1][0] <= 1.02 and ratios[-1][1] < slow.delay_s / 4:
            break
    assert any(r <= 1.02 and m < slow.delay_s / 4 for r, m in ratios), \
        f"in-flight ticks stayed degraded across attempts: {ratios}"


# ---------------------------------------------------------------------------
# CLI: fleet start / worker / status / drain
# ---------------------------------------------------------------------------

def test_cli_fleet_round_trip(tmp_path, capsys):
    db = tmp_path / "db.jsonl"
    fleet = tmp_path / "fleet"
    rc = tunedb_main([
        "fleet", "start", "--fleet", str(fleet), "--store", str(db),
        "--space", "gemm", "--shape", "M=512,N=128,K=512", "--drain"])
    assert rc == 0
    assert "published 1 job(s)" in capsys.readouterr().out

    rc = tunedb_main(["fleet", "status", "--fleet", str(fleet)])
    assert rc == 0
    status = json.loads(capsys.readouterr().out)
    assert status["counts"]["queue"] == 1 and status["draining"]

    rc = tunedb_main([
        "fleet", "worker", "--fleet", str(fleet), "--worker-id", "cli-w",
        "--train-samples", "400", "--epochs", "2", "--no-remeasure"])
    assert rc == 0
    assert "1 tuned" in capsys.readouterr().out

    rc = tunedb_main(["fleet", "drain", "--fleet", str(fleet), "--wait",
                      "--timeout", "30"])
    assert rc == 0
    out = capsys.readouterr().out
    report = json.loads((fleet / "report.json").read_text())
    assert report["done"] == 1 and report["failed"] == 0
    assert report["workers"] == ["cli-w"]
    store = RecordStore.open(db)
    assert store.contains("gemm", gemm_input(512, 128, 512))
    assert store.get("gemm", gemm_input(512, 128, 512)).merged_from == "cli-w"
    assert "\"done\": 1" in out


def test_cli_fleet_status_rejects_non_fleet_dir(tmp_path):
    with pytest.raises(FileNotFoundError):
        tunedb_main(["fleet", "status", "--fleet", str(tmp_path / "nope")])


# ---------------------------------------------------------------------------
# PR 5 satellites: priority claiming, shard GC, --workers spawner
# ---------------------------------------------------------------------------

def test_workers_claim_hottest_jobs_first(tmp_path):
    _, coord = _fleet(tmp_path)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0), count=1),
                   FleetJob(space="gemm", inputs=_shape(1), count=50),
                   FleetJob(space="gemm", inputs=_shape(2), count=5)])
    order = []
    for _ in range(3):
        job, lease = coord.fleet.claim()
        order.append(job.count)
        lease.unlink()
    assert order == [50, 5, 1]           # hottest telemetry count first
    assert coord.fleet.claim() is None


def test_requeued_job_keeps_its_priority(tmp_path):
    _, coord = _fleet(tmp_path)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0), count=7)])
    job, lease = coord.fleet.claim()
    coord.fleet.fail(job, lease, "synthetic", max_attempts=3)
    job2, lease2 = coord.fleet.claim()
    assert job2.count == 7 and job2.attempts == 1
    lease2.unlink()


def test_claim_priority_updates_on_republication(tmp_path):
    """A republished job (retune of a completed shape) with a hotter count
    must not be ordered by its stale cached priority."""
    _, coord = _fleet(tmp_path)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0), count=5),
                   FleetJob(space="gemm", inputs=_shape(1), count=10)])
    job, lease = coord.fleet.claim()     # caches shape(1) at count=10
    assert job.count == 10
    coord.fleet.complete(job, lease, {})
    assert coord.publish([FleetJob(space="gemm", inputs=_shape(1),
                                   count=500)], force=True) == 1
    job2, lease2 = coord.fleet.claim()
    assert job2.count == 500             # fresh file invalidated the cache
    lease2.unlink()


def test_retune_fleet_jobs_carry_telemetry_counts(tmp_path):
    store = RecordStore.open(tmp_path / "db.jsonl")
    controller = RetuneController(
        store, tuners={"gemm": StubTuner(fixed_cfg=True)},
        fleet_dir=tmp_path / "fleet", fleet_timeout_s=0.2, fleet_poll_s=0.02,
        cfg=RetuneConfig(min_calls=8, top_k_shapes=2))
    _drive_traffic(get_telemetry(), _shape(0), n=40)
    controller.maybe_retune()            # submits; no workers: will time out
    assert controller.wait_async(timeout=30.0) is not None
    jobs = sorted((tmp_path / "fleet" / "queue").glob("*.json"))
    assert jobs, "the drift-triggered plan published nothing"
    published = [json.loads(p.read_text()) for p in jobs]
    assert any(j["count"] == 40 for j in published)


def test_drain_compact_archives_cursor_complete_shards(tmp_path):
    store, coord = _fleet(tmp_path)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0)),
                   FleetJob(space="gemm", inputs=_shape(1))])
    worker = Worker(tmp_path / "fleet", worker_id="w0",
                    tuners={"gemm": StubTuner(n_measured=2)})
    assert worker.run_one() and worker.run_one()
    coord.poll()                         # merge both records
    assert len(store) == 2
    shard_dir = coord.fleet.shard_dir()
    assert list(shard_dir.glob("*.jsonl"))

    archived = coord.compact_shards()
    assert archived == ["w0"]
    assert not list(shard_dir.glob("*.jsonl"))
    assert (shard_dir / "archive" / "w0.jsonl").exists()
    assert not (tmp_path / "fleet" / "merged" / "w0.json").exists()

    # a returning worker with the SAME id starts a fresh shard; the reset
    # cursor merges it from byte 0 — nothing skipped, nothing duplicated
    coord.publish([FleetJob(space="gemm", inputs=_shape(2))])
    worker2 = Worker(tmp_path / "fleet", worker_id="w0",
                     tuners={"gemm": StubTuner()})
    assert worker2.run_one()
    coord.poll()
    assert store.contains("gemm", _shape(2)) and len(store) == 3


def test_compact_skips_shards_with_unmerged_bytes(tmp_path):
    store, coord = _fleet(tmp_path)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    worker = Worker(tmp_path / "fleet", worker_id="w0",
                    tuners={"gemm": StubTuner()})
    assert worker.run_one()
    assert coord.compact_shards() == []  # nothing merged yet: must stay
    coord.poll()
    assert coord.compact_shards() == ["w0"]
    assert len(store) == 1


def test_cli_drain_compact(tmp_path, capsys):
    db, fleet = tmp_path / "db.jsonl", tmp_path / "fleet"
    store = RecordStore.open(db)
    coord = Coordinator(fleet, store)
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    worker = Worker(fleet, worker_id="w0", tuners={"gemm": StubTuner()})
    assert worker.run_one()
    rc = tunedb_main(["fleet", "drain", "--fleet", str(fleet), "--wait",
                      "--timeout", "30", "--compact"])
    assert rc == 0
    assert "compacted 1 merged shard(s)" in capsys.readouterr().out
    shard_dir = coord.fleet.shard_dir()
    assert not list(shard_dir.glob("*.jsonl"))
    assert (shard_dir / "archive" / "w0.jsonl").exists()
    assert RecordStore.open(db).contains("gemm", _shape(0))


def test_cli_drain_compact_without_wait(tmp_path, capsys):
    """--compact must act (merge what landed, then archive) even without
    --wait — never a silent no-op."""
    db, fleet = tmp_path / "db.jsonl", tmp_path / "fleet"
    coord = Coordinator(fleet, RecordStore.open(db))
    coord.publish([FleetJob(space="gemm", inputs=_shape(0))])
    worker = Worker(fleet, worker_id="w0", tuners={"gemm": StubTuner()})
    assert worker.run_one()
    rc = tunedb_main(["fleet", "drain", "--fleet", str(fleet), "--compact"])
    assert rc == 0
    assert "compacted 1 merged shard(s)" in capsys.readouterr().out
    assert not list(coord.fleet.shard_dir().glob("*.jsonl"))
    assert RecordStore.open(db).contains("gemm", _shape(0))


def test_fleet_start_spawns_local_workers(tmp_path, monkeypatch, capsys):
    """--workers N forks N `fleet worker` subprocesses against the bus,
    implies drain+wait, and reaps the children before returning."""
    import subprocess

    spawned = []

    class _FakeProc:
        def __init__(self, cmd):
            self.cmd = cmd
            self.pid = 4000 + len(spawned)

        def wait(self, timeout=None):
            return 0

        def terminate(self):
            raise AssertionError("healthy fake workers are never terminated")

    def fake_popen(cmd, **kw):
        proc = _FakeProc(cmd)
        spawned.append(proc)
        return proc

    monkeypatch.setattr(subprocess, "Popen", fake_popen)
    db, fleet = tmp_path / "db.jsonl", tmp_path / "fleet"
    rc = tunedb_main(["fleet", "start", "--fleet", str(fleet),
                      "--store", str(db), "--workers", "2",
                      "--worker-train-samples", "300", "--worker-epochs", "2",
                      "--timeout", "5"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "spawned 2 local worker process(es)" in out
    assert len(spawned) == 2
    for proc in spawned:
        assert proc.cmd[1:4] == ["-m", "repro.tunedb", "fleet"]
        assert "worker" in proc.cmd
        assert str(fleet) in proc.cmd
        assert "300" in proc.cmd and "2" in proc.cmd
    # one-command mode marks the plan final so the workers exit on empty
    from repro.tunedb.fleet import FleetDir
    assert FleetDir(fleet).draining()
