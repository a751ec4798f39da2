"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and yields the ``generate`` calls of a closed
loop (the next call starts when the last returns).

Every seed gets the same set of prompt lengths in every call, in another
order, and its own token ids: the seed changes what is computed, never how
much.  Prompt lengths are the ``requests_per_call`` stratified quantiles of
a lognormal (median, sigma), clipped to [min, max] and rounded up to a
multiple of ``grid``: each distinct length is one compiled prefill program.
"""

from __future__ import annotations

import dataclasses
import json
import math
import pathlib
import statistics
from typing import Dict, List

import numpy as np

TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclasses.dataclass(frozen=True)
class Mix:
    name: str
    requests_per_call: int
    max_new: int
    median: float
    sigma: float
    min_len: int
    max_len: int
    grid: int

    @classmethod
    def load(cls, name: str) -> "Mix":
        path = TRAFFIC_DIR / f"{name}.json"
        raw = json.loads(path.read_text())
        if raw.get("loop") != "closed":
            raise ValueError(f"mix {name}: only closed-loop traffic exists")
        pl = raw["prompt_len"]
        return cls(name=name, requests_per_call=int(raw["requests_per_call"]),
                   max_new=int(raw["max_new"]), median=float(pl["median"]),
                   sigma=float(pl["sigma"]), min_len=int(pl["min"]),
                   max_len=int(pl["max"]), grid=int(pl["grid"]))

    def lengths(self) -> List[int]:
        """The prompt lengths of one call, sorted (the same for every seed)."""
        normal = statistics.NormalDist()
        out = []
        for i in range(self.requests_per_call):
            u = (i + 0.5) / self.requests_per_call
            x = math.exp(math.log(self.median) + self.sigma * normal.inv_cdf(u))
            x = min(max(x, self.min_len), self.max_len)
            out.append(int(math.ceil(x / self.grid) * self.grid))
        return out

    def longest_request(self) -> int:
        """Prompt plus generated tokens of the longest request."""
        return max(self.lengths()) + self.max_new


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one stream of one seed; any whole number is a seed."""
    return np.random.default_rng([int(seed) % 2 ** 64, *stream])


class Calls:
    """The seeded sequence of ``generate`` calls of one mix."""

    def __init__(self, mix: Mix, vocab: int, seed: int) -> None:
        self.mix, self.vocab, self.seed = mix, vocab, seed
        self._lengths = np.asarray(mix.lengths())

    def call(self, index: int) -> List[np.ndarray]:
        """The prompts of call ``index`` (token ids uniform over the vocab)."""
        rng = seed_rng(self.seed, 1, index)
        order = rng.permutation(self._lengths)
        return [rng.integers(0, self.vocab, int(n), dtype=np.int32)
                for n in order]

    def stats(self) -> Dict[str, int]:
        lens = self._lengths
        return {"requests_per_call": int(lens.size),
                "prompt_tokens_per_call": int(lens.sum()),
                "distinct_lengths": int(np.unique(lens).size)}
