"""The profiler trace of a run: taking it, and reducing it to device busy
time, device operations and idle gaps on the host's clock.

On a TPU the trace has one plane per chip (``/device:TPU:<n>``) whose
``XLA Ops`` line holds one event per executed HLO instruction, named by the
instruction's text (``%name = <shape> <op>(<operands>), ...``).  Control
flow nests: a ``while`` event spans the events of its body.  Host threads
share the trace's clock, so the annotation the benchmark opens around the
traced window maps ``time.perf_counter`` onto it.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import re
import threading
import time
from typing import Iterable, List, NamedTuple, Optional, Sequence, Tuple

ANNOTATION = "bench.window"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
CONTAINERS = ("while", "conditional", "call")

_ARRAY = re.compile(r"\b([a-z]+\d*)\[([\d,]*)\](?:\{([^{}]*)\})?")
_SPACE = re.compile(r"S\((\d+)\)")
_OP = re.compile(r"^%\S+\s*=\s*(.*?)\s+([a-z][\w\-]*)\(")
HBM = 0


class Array(NamedTuple):
    """An array of an instruction: its type, dims and memory space, the
    layout's ``S(n)``: ``S(1)`` marks an array the compiler keeps in the
    core's on-chip VMEM, and a layout without one is in HBM (0)."""

    dtype: str
    dims: Tuple[int, ...]
    space: int


def _arrays(text: str) -> List[Array]:
    out = []
    for dtype, dims, layout in _ARRAY.findall(text):
        space = _SPACE.search(layout)
        out.append(Array(dtype, tuple(int(x) for x in dims.split(",") if x),
                         int(space.group(1)) if space else HBM))
    return out


@dataclasses.dataclass(frozen=True)
class Op:
    """One device operation, on the host's clock (perf_counter seconds)."""

    chip: int
    name: str
    t0: float
    dur: float

    @property
    def t1(self) -> float:
        return self.t0 + self.dur

    @property
    def kind(self) -> str:
        m = _OP.match(self.name)
        return m.group(2) if m else self.name.split("(")[0][:40]

    def label(self) -> str:
        """The op kind and its output shape: stable across recompiles."""
        m = _OP.match(self.name)
        if not m:
            return self.name[:80]
        shape = re.sub(r"\{[^{}]*\}", "", m.group(1))
        return f"{m.group(2)} {shape}"[:80]

    def results(self) -> List[Array]:
        """The result's arrays (one, or each of a tuple's)."""
        m = _OP.match(self.name)
        return _arrays(m.group(1)) if m else []

    def operands(self) -> List[Array]:
        """Each operand's array, read from the instruction text."""
        m = _OP.match(self.name)
        if not m:
            return []
        start = m.end()
        depth, i = 1, start
        while i < len(self.name) and depth:
            c = self.name[i]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
            i += 1
        return _arrays(self.name[start:i - 1])


@dataclasses.dataclass
class DeviceTrace:
    """Device operations inside the traced window, on the host's clock."""

    window: Tuple[float, float]     # perf_counter seconds
    ops: List[Op]                   # leaf operations (no control flow)
    chips: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def busy_intervals(self, chip: Optional[int] = None
                       ) -> List[Tuple[float, float]]:
        """Union of the operations' intervals, clipped to the window."""
        lo, hi = self.window
        ivs = sorted((max(o.t0, lo), min(o.t1, hi)) for o in self.ops
                     if chip is None or o.chip == chip)
        out: List[Tuple[float, float]] = []
        for a, b in ivs:
            if b <= a:
                continue
            if out and a <= out[-1][1]:
                if b > out[-1][1]:
                    out[-1] = (out[-1][0], b)
            else:
                out.append((a, b))
        return out

    def busy_s(self) -> float:
        """Busy seconds averaged over the chips used."""
        total = sum(b - a for c in range(self.chips)
                    for a, b in self.busy_intervals(c))
        return total / max(self.chips, 1)

    def idle_gaps(self, chip: int = 0) -> List[Tuple[float, float]]:
        lo, hi = self.window
        gaps, t = [], lo
        for a, b in self.busy_intervals(chip):
            if a > t:
                gaps.append((t, a))
            t = max(t, b)
        if hi > t:
            gaps.append((t, hi))
        return gaps


def _is_container(name: str) -> bool:
    m = _OP.match(name)
    return bool(m) and m.group(2) in CONTAINERS


def reduce_profile(planes: Iterable, anchor_pc: float) -> DeviceTrace:
    """A :class:`DeviceTrace` from the planes of a ``ProfileData``.

    ``anchor_pc`` is ``time.perf_counter()`` read as the ``bench.window``
    annotation opened; the annotation's own start and length give the
    window and the offset between the trace's clock and the host's."""
    planes = list(planes)
    ann = None
    for plane in planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == ANNOTATION:
                        ann = ev
    if ann is None:
        raise RuntimeError(f"the trace has no {ANNOTATION!r} annotation")
    offset = anchor_pc - ann.start_ns * 1e-9
    window = (anchor_pc, anchor_pc + ann.duration_ns * 1e-9)
    ops: List[Op] = []
    chips = set()
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        chip = int(m.group(1))
        chips.add(chip)
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if _is_container(ev.name):
                    continue
                ops.append(Op(chip, ev.name, ev.start_ns * 1e-9 + offset,
                              ev.duration_ns * 1e-9))
    return DeviceTrace(window=window, ops=ops, chips=max(len(chips), 1))


def load(log_dir: str, anchor_pc: float) -> DeviceTrace:
    from jax.profiler import ProfileData

    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if not paths:
        raise RuntimeError(f"no profile written under {log_dir}")
    return reduce_profile(ProfileData.from_file(paths[0]).planes, anchor_pc)


class TraceWindow:
    """Takes a profiler trace of ``length_s`` seconds, starting
    ``delay_s`` after :meth:`start`, on a thread of its own while the
    serving loop runs on the caller's."""

    def __init__(self, log_dir: str, delay_s: float, length_s: float) -> None:
        self.log_dir, self.delay_s, self.length_s = log_dir, delay_s, length_s
        self.anchor_pc: Optional[float] = None
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._run, daemon=True)

    def start(self) -> "TraceWindow":
        self._thread.start()
        return self

    def _run(self) -> None:
        import jax

        try:
            time.sleep(self.delay_s)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            try:
                with jax.profiler.TraceAnnotation(ANNOTATION):
                    self.anchor_pc = time.perf_counter()
                    time.sleep(self.length_s)
            finally:
                jax.profiler.stop_trace()
        except BaseException as e:          # reported by join()
            self.error = e

    def join(self) -> DeviceTrace:
        self._thread.join()
        if self.error is not None:
            raise RuntimeError(f"profiler trace failed: {self.error}")
        return load(self.log_dir, self.anchor_pc)


def _innermost(spans: Sequence) -> List[Tuple[float, float, object]]:
    """Host time cut into pieces, each with the innermost span over it
    (None where no span is open), in order."""
    edges = sorted({t for s in spans for t in (s.t0, s.t1)})
    out, i, open_ = [], 0, []
    spans = sorted(spans, key=lambda s: s.t0)
    for lo, hi in zip(edges, edges[1:]):
        while i < len(spans) and spans[i].t0 <= lo:
            open_.append(spans[i])
            i += 1
        open_ = [s for s in open_ if s.t1 > lo]
        inner = min(open_, key=lambda s: s.dur) if open_ else None
        out.append((lo, hi, inner))
    return out


def attribute_gaps(gaps: Sequence[Tuple[float, float]],
                   spans: Sequence, ops: Sequence[Op],
                   top: int = 10) -> List[List]:
    """Idle seconds by what the host was doing: each gap is cut at span
    boundaries and each piece goes to the innermost span around it, marked
    by whether the span's device work had started yet."""
    pieces = _innermost(spans)
    starts = [p[0] for p in pieces]
    op_starts = sorted(o.t0 for o in ops)
    totals: dict = {}

    def add(label, dt):
        totals[label] = totals.get(label, 0.0) + dt

    for a, b in gaps:
        t = a
        j = max(bisect.bisect_right(starts, a) - 1, 0)
        while t < b:
            if j >= len(pieces) or pieces[j][0] > t:
                nxt = pieces[j][0] if j < len(pieces) else b
                end = min(b, nxt)
                add("host outside engine spans", end - t)
                t = end
                continue
            lo, hi, inner = pieces[j]
            end = min(b, hi)
            if end > t:
                if inner is None:
                    add("host outside engine spans", end - t)
                else:
                    k = bisect.bisect_left(op_starts, inner.t0)
                    began = k < len(op_starts) and op_starts[k] < (t + end) / 2
                    add(f"{inner.name}: host after its device work began"
                        if began else
                        f"{inner.name}: host before its device work", end - t)
                t = end
            j += 1
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])
            ][:top]


def top_ops(ops: Sequence[Op], top: int = 10) -> List[List]:
    """Device seconds by operation kind and output shape, largest first."""
    totals: dict = {}
    for o in ops:
        k = o.label()
        totals[k] = totals.get(k, 0.0) + o.dur
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])
            ][:top]
