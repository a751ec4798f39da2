"""Wall-clock kernel measurement on the serving path (paper §6, closed).

The paper's loop is measure → model → *re-measure*: the model proposes a
top-k, real measurements pick the winner.  Offline tools pass a backend's
``measure`` straight into ``ModelSet(measurer=...)`` and pay the
measurements inline at resolution time.  A serving engine cannot — a
dispatch resolution sits on the decode path — so this module splits the
recipe in two:

* :class:`ServingMeasurer` — the ``(space, cfg, inputs) -> TFLOPS``
  callable wired as ``ModelSet.measurer`` behind
  ``ServeConfig(measure="wallclock")``.  ``"wallclock"`` times the
  compiled kernels via :class:`~repro.core.backend.WallClockBackend` and
  exists only on a TPU: it raises without one, and for a space wall-clock
  timing does not cover, rather than hand back a simulated number under
  its name.  ``"sim"`` uses the analytic
  :class:`~repro.core.backend.SimulatedTPUBackend` anywhere.  Every
  measurement increments ``tunedb_measurements_total{backend}`` and, when
  tracing is on, records a ``measure.wallclock`` / ``measure.sim`` span — so the
  Perfetto view shows the tuner's measurements on the same clock as the
  decode ticks they stole time from.

* :class:`MeasureQueue` — the idle-decode-gap scheduler.  With a queue
  attached (``ModelSet.measure_queue``), ``ModelSet.predict`` serves the
  model argmax *immediately* and enqueues the top-k candidates here; the
  engine's controller poll drains a few items per decode tick
  (:meth:`process`), re-measures the candidates, and commits the measured
  winner back into the ModelSet memo **and** the live plan overlay — the
  next resolution of that shape serves the measured config with a plan
  probe, and no decode tick ever blocked on a measurement.
"""

from __future__ import annotations

import threading
from collections import deque
from typing import Deque, Dict, List, Mapping, Optional, Tuple

from .store import normalize_inputs

__all__ = ["MeasureQueue", "ServingMeasurer"]

MEASURE_MODES = ("wallclock", "sim")


def _count_measurement(backend: str) -> None:
    try:
        from .obs.metrics import get_registry
        get_registry().counter(
            "tunedb_measurements_total",
            "serving-path kernel measurements by backend").inc(
                backend=backend)
    except Exception:
        pass                    # observability never blocks a measurement


class ServingMeasurer:
    """``ModelSet.measurer`` for a serving process: the compiled kernel's
    wall clock on a TPU, or the simulator, spans + counters either way."""

    def __init__(self, mode: str = "wallclock", *, warmup: int = 1,
                 iters: int = 3) -> None:
        if mode not in MEASURE_MODES:
            raise ValueError(f"measure mode {mode!r}; pick one of "
                             f"{MEASURE_MODES}")
        from repro.core.backend import SimulatedTPUBackend, WallClockBackend
        if mode == "wallclock":
            import jax
            if jax.default_backend() != "tpu":
                raise RuntimeError(
                    "measure='wallclock' times compiled kernels on a TPU; "
                    f"JAX's backend here is {jax.default_backend()!r} "
                    "(use measure='sim' off the chip)")
            self._backend = WallClockBackend(warmup=warmup, iters=iters)
        else:
            self._backend = SimulatedTPUBackend(noise=0.0)
        self.mode = mode
        self.counts: Dict[str, int] = {"wallclock": 0, "sim": 0}

    def __call__(self, space: str, cfg: Mapping[str, int],
                 inputs: Mapping[str, int]) -> float:
        label = self.mode
        from .obs import trace as _trace
        tr = _trace._TRACER
        ctx = None
        if tr is not None:
            shape = ",".join(f"{k}={v}" for k, v in sorted(inputs.items()))
            name = f"measure.{label}"
            ctx = tr.span(name, space=space, shape=shape)
            if ctx is _trace._NULL_SPAN:
                # no open trace on this thread (engine-init calibration,
                # offline tools): measurements are rare and are exactly
                # what the profiling harness exists to show — always keep
                ctx = tr.root(name, trace_id=_trace.new_trace_id(),
                              space=space, shape=shape)
        if ctx is not None:
            with ctx as sp:
                tflops = self._measure(space, cfg, inputs)
                if sp is not None:
                    sp.attrs["backend"] = label
                    sp.attrs["tflops"] = round(float(tflops), 3)
        else:
            tflops = self._measure(space, cfg, inputs)
        return tflops

    def _measure(self, space: str, cfg: Mapping[str, int],
                 inputs: Mapping[str, int]) -> float:
        # wall clock covers GEMM only: another space raises
        # NotImplementedError here, never a simulated number
        tflops = float(self._backend.measure(space, cfg, inputs))
        self.counts[self.mode] += 1
        _count_measurement(self.mode)
        return tflops

    def stats(self) -> Dict[str, object]:
        return {"mode": self.mode, "counts": dict(self.counts)}


class MeasureQueue:
    """Thread-safe backlog of deferred §6 top-k re-measurements.

    ``push`` comes from ``ModelSet.predict`` (dispatch path — must be
    cheap: one lock, one dedupe probe, one append).  ``process`` runs in
    idle decode gaps, driven by the engine's controller poll."""

    def __init__(self, maxlen: int = 256) -> None:
        self._lock = threading.Lock()
        self._items: Deque[tuple] = deque()
        self._queued: set = set()
        self.maxlen = maxlen
        self.pushed = 0
        self.processed = 0
        self.dropped = 0                # queue-full discards
        self.upgrades = 0               # measured winner beat the argmax

    def __len__(self) -> int:
        with self._lock:
            return len(self._items)

    def push(self, space: str, backend: Optional[str],
             inputs: Mapping[str, int],
             candidates: List[Dict[str, int]]) -> bool:
        key = (space, backend, tuple(sorted(inputs.items())))
        with self._lock:
            if key in self._queued:
                return False
            if len(self._items) >= self.maxlen:
                self.dropped += 1
                return False
            self._queued.add(key)
            self._items.append((key, space, backend, dict(inputs),
                                [dict(c) for c in candidates]))
            self.pushed += 1
        return True

    def _pop(self) -> Optional[tuple]:
        with self._lock:
            if not self._items:
                return None
            item = self._items.popleft()
            self._queued.discard(item[0])
            return item

    def process(self, measurer, *, models=None, max_items: int = 2) -> int:
        """Re-measure up to ``max_items`` pending shapes; commit each
        measured winner into the ModelSet memo and the live plan overlay.
        Returns shapes processed.  A failing candidate measurement skips
        that candidate, never the decode tick driving this."""
        done = 0
        while done < max_items:
            item = self._pop()
            if item is None:
                break
            _key, space, backend, inputs, candidates = item
            measured: List[Tuple[Dict[str, int], float]] = []
            for cfg in candidates:
                try:
                    measured.append((cfg,
                                     float(measurer(space, cfg, inputs))))
                except Exception:
                    continue
            done += 1
            self.processed += 1
            if not measured:
                continue
            cfg, tflops = max(measured, key=lambda t: t[1])
            if candidates and cfg != candidates[0]:
                self.upgrades += 1
            if models is not None:
                try:
                    models.apply_measurement(space, backend, inputs, cfg,
                                             tflops)
                except Exception:
                    pass
            self._promote_plan(space, inputs, cfg)
        return done

    @staticmethod
    def _promote_plan(space: str, inputs: Mapping[str, int],
                      cfg: Mapping[str, int]) -> None:
        """Overwrite the shape's plan-overlay entry with the measured
        winner, so the frozen fast path serves it from the next call on.
        Only when the plan still belongs to the live store generation —
        a stood-aside plan will be recompiled anyway."""
        try:
            from .store import serving_state
            state = serving_state()
            plan, store = state.plan, state.store
            if plan is None:
                return
            if store is not None and store.version != plan.store_version:
                return
            key = tuple(sorted(normalize_inputs(inputs).items()))
            plan.promote(space, key, cfg, "model")
        except Exception:
            pass

    def stats(self) -> Dict[str, object]:
        with self._lock:
            backlog = len(self._items)
        return {"backlog": backlog, "pushed": self.pushed,
                "processed": self.processed, "dropped": self.dropped,
                "upgrades": self.upgrades, "maxlen": self.maxlen}
