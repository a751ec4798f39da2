"""Whether what the timed path served is correct.

Once the window has closed, a sample of the requests it finished, drawn
from the seed and holding the longest, is run through the configuration's
plain float32 reference: each prompt with the tokens served for it.  The
number compared is the widest gap by which a served token's logit lies
below the reference's best logit at that position (greedy decoding serves
the best; a gap is what the program's lower precision cost).  The control
reads, at the same positions, the gap of the token that the reference put
first when computed in float8.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import pathlib
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from .traffic import seed_rng

CELL_DIR = pathlib.Path(__file__).resolve().parent / "cells"


@dataclasses.dataclass(frozen=True)
class Limits:
    max_logit_gap: float    # logit units
    sample_tokens: int      # served tokens compared, at least

    @classmethod
    def load(cls, cell: str) -> "Limits":
        path = CELL_DIR / f"{cell}.json"
        raw = json.loads(path.read_text())
        return cls(max_logit_gap=float(raw["max_logit_gap"]),
                   sample_tokens=int(raw["sample_tokens"]))


def sample(served: Sequence[Tuple[np.ndarray, List[int]]], seed: int,
           tokens: int, per_call: int, max_new: int) -> List[int]:
    """Indices of the requests to compare: the longest, and one request
    from each of ``k`` equal ranges of the position in its call (so of the
    slot it was served in), each from a call drawn from the seed, with
    ``k * max_new >= tokens`` and ``k >= 2``."""
    if not served:
        return []
    sizes = [len(p) + len(o) for p, o in served]
    chosen = [int(np.argmax(sizes))]
    rng = seed_rng(seed, 2)
    calls = len(served) // per_call
    k = max(2, -(-tokens // max_new))
    bounds = np.linspace(0, per_call, k + 1).astype(int)
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        pos = int(rng.integers(lo, max(hi, lo + 1)))
        i = int(rng.integers(calls)) * per_call + pos
        if i not in chosen:
            chosen.append(i)
    return chosen


def reference_for(spec, weights, length: int, n_positions: int,
                  fp8: bool = False):
    module = importlib.import_module(f"bench.reference.{spec.reference}")
    return module.Reference(spec, weights, length, n_positions, fp8=fp8)


def compare(spec, weights, served, seed: int, limits: Limits, length: int,
            max_new: int, per_call: int, control: bool = False
            ) -> Dict[str, float]:
    """Readings over the sample: ``max_logit_gap`` of the served tokens,
    ``tokens_compared``, and with ``control`` the float8 reference's
    ``control_max_logit_gap`` at the same positions."""
    ref = reference_for(spec, weights, length, max_new)
    low = reference_for(spec, weights, length, max_new, fp8=True) \
        if control else None
    gap = gap_c = 0.0
    compared = 0
    for i in sample(served, seed, limits.sample_tokens, per_call,
                    max_new):
        prompt, out = served[i]
        if not out:
            continue
        toks = np.concatenate([np.asarray(prompt, np.int32),
                               np.asarray(out[:-1], np.int32)])
        pos = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        want = ref.logits(toks, pos)
        best = want.max(-1)
        got = jnp.take_along_axis(want, jnp.asarray(out)[:, None], -1)[:, 0]
        gap = max(gap, float((best - got).max()))
        compared += len(out)
        if low is not None:
            pick = low.logits(toks, pos).argmax(-1)
            alt = jnp.take_along_axis(want, pick[:, None], -1)[:, 0]
            gap_c = max(gap_c, float((best - alt).max()))
    out = {"max_logit_gap": gap, "tokens_compared": float(compared)}
    if control:
        out["control_max_logit_gap"] = gap_c
    return out


def checks(readings: Dict[str, float], limits: Limits, short: int
           ) -> Dict[str, Dict[str, float]]:
    """Each number compared, with its limit; ``correct`` holds when every
    value is within its limit (``tokens_compared`` is a floor)."""
    return {
        "max_logit_gap": {"value": readings["max_logit_gap"],
                          "limit": limits.max_logit_gap},
        "short_answers": {"value": float(short), "limit": 0.0},
        "tokens_compared": {"value": readings["tokens_compared"],
                            "limit": float(limits.sample_tokens)},
    }


def control_checks(readings: Dict[str, float], limits: Limits
                   ) -> Dict[str, Dict[str, float]]:
    """The control held to the cell's limits: the float8 reference's gap
    in the place of the served tokens', over the same positions."""
    return checks({**readings,
                   "max_logit_gap": readings["control_max_logit_gap"]},
                  limits, 0)


def passed(checked: Dict[str, Dict[str, float]]) -> bool:
    ok = True
    for name, c in checked.items():
        if name == "tokens_compared":
            ok &= c["value"] >= c["limit"]
        else:
            ok &= c["value"] <= c["limit"]
    return bool(ok)
