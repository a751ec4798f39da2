"""What a run observed, handed to each per-layer metric's reader.

A reader is ``bench/metrics/<metric>.py`` with ``read(obs) -> float|None``;
it returns None when the run holds nothing for it to read, and the metric
is then left out of the result line.
"""

from __future__ import annotations

import bisect
import dataclasses
import importlib
from typing import Any, Dict, List, Optional, Sequence, Tuple

PREFILL, TICK = "engine.prefill", "engine.tick"


@dataclasses.dataclass(frozen=True)
class HostSpan:
    """One of the program's spans, on the host's clock (perf_counter s)."""

    name: str
    t0: float
    dur: float
    attrs: Dict[str, Any]

    @property
    def t1(self) -> float:
        return self.t0 + self.dur


@dataclasses.dataclass(frozen=True)
class Request:
    prompt_len: int
    served: int             # tokens returned


@dataclasses.dataclass
class Observations:
    spec: Any                           # model.ModelSpec
    peak: Any                           # peaks.Peak
    window: Tuple[float, float]         # measured window, perf_counter s
    requests: List[Request]             # every request of the window
    spans: List[HostSpan]               # the program's spans in the window
    trace: Optional[Any] = None         # devtrace.DeviceTrace

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def named(self, name: str) -> List[HostSpan]:
        return [s for s in self.spans if s.name == name]

    def gemm_rows(self, span: HostSpan) -> Optional[int]:
        """Rows (M) of the projection GEMMs a span's device work runs."""
        if span.name == PREFILL:
            return int(span.attrs["prompt_len"])
        if span.name == TICK:
            return self.spec.slots
        return None


class SpanIndex:
    """Finds the span around an interval among spans that do not overlap."""

    def __init__(self, spans: Sequence[HostSpan]) -> None:
        self.spans = sorted(spans, key=lambda s: s.t0)
        self.starts = [s.t0 for s in self.spans]

    def around(self, t0: float, t1: float) -> Optional[HostSpan]:
        i = bisect.bisect_right(self.starts, t0) - 1
        if i >= 0 and t1 <= self.spans[i].t1:
            return self.spans[i]
        return None


def read_metric(name: str, obs: Observations) -> Optional[float]:
    module = importlib.import_module(f"bench.metrics.{name}")
    value = module.read(obs)
    return None if value is None else float(value)
