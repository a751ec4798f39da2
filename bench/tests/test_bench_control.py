"""The control at a size a test run holds: what the engine serves in bf16
lies well inside the float8 reference's gap, on several seeds, and at this
size's limit the comparison that decides ``correct`` passes the engine and
fails the control.  (On the chip, at each cell's size,
``bench/calibrate.py`` reads the same two numbers that each cell's limit
is set from, and judges them the same way.)"""

import numpy as np
import pytest

from bench.correctness import Limits, checks, compare, control_checks, passed
from bench.model import ModelSpec, seed_key

from .conftest import TINY_CONFIG

SPEC = ModelSpec.from_dict({
    **TINY_CONFIG,
    "config": {**TINY_CONFIG["config"], "hidden_size": 256,
               "intermediate_size": 512, "head_dim": 64,
               "vocab_size": 4096},
    "serve": {"slots": 8, "max_len": 128}})
# Set as a cell's limit is, from this size's readings on the CPU over seeds
# 1-11 and 2**31 + 7: the served gap reads 0-0.0111, the control's
# 0.0834-0.160, and 0.0111^0.4 * 0.0834^0.6 = 0.037.
LIMITS = Limits(max_logit_gap=0.04, sample_tokens=64)


@pytest.fixture(scope="module")
def engine():
    from repro.serve.engine import Engine, ServeConfig

    return Engine(SPEC.program_config(), SPEC.make_weights(seed_key(0)),
                  ServeConfig(slots=8, max_len=128))


@pytest.mark.parametrize("seed", [1, 2, 2 ** 31 + 7])
def test_float8_control_reads_far_above_the_served_gap(engine, seed):
    w = SPEC.make_weights(seed_key(seed))
    engine.params = w
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 4096, int(n), dtype=np.int32)
               for n in rng.integers(16, 64, 8)]
    outs = engine.generate(prompts, max_new=16)
    r = compare(SPEC, w, list(zip(prompts, outs)), seed, LIMITS,
                length=128, max_new=16, per_call=8, control=True)
    assert r["tokens_compared"] >= 64
    assert r["control_max_logit_gap"] > 3 * r["max_logit_gap"], r
    assert passed(checks(r, LIMITS, 0)), r
    assert not passed(control_checks(r, LIMITS)), r
