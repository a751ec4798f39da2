"""A whole run of a tiny cell on the CPU, the look for a chip skipped, with
the timed path broken underneath: ``correct`` has to come out false for
each fault a serving cell can have.  (A one-chip cell has no exchange
between chips to leave out.)"""

import time

import jax.numpy as jnp
import numpy as np
import pytest

from bench.harness import NoDevice, check_device, run


def sound(eng):
    return eng


def state_unchanged(eng):
    """The decode step hands back the cache it was given."""
    step = eng._decode
    eng._decode = lambda p, t, c, i: (step(p, t, c, i)[0], c)
    return eng


def half_batch(eng):
    """The second half of the slots gets the first half's logits."""
    step = eng._decode

    def broken(p, t, c, i):
        logits, cache = step(p, t, c, i)
        h = logits.shape[0] // 2
        return logits.at[h:].set(logits[: logits.shape[0] - h]), cache
    eng._decode = broken
    return eng


def token_altered(eng):
    """Every fourth decode tick samples the next token id instead."""
    sample = eng._sample
    calls = [0]

    def broken(logits):
        calls[0] += 1
        toks = sample(logits)
        if calls[0] % 4 == 0:
            toks = (toks + 1) % logits.shape[-1]
        return toks
    eng._sample = broken
    return eng


@pytest.mark.parametrize("fault,correct", [
    (sound, True), (state_unchanged, False), (half_batch, False),
    (token_altered, False)])
def test_a_broken_timed_path_is_not_correct(tiny_cell, fault, correct):
    from repro.serve.engine import Engine

    def factory(cfg, params, sc):
        return fault(Engine(cfg, params, sc))

    result = run("tiny.mix", 2 ** 31 + 11, 0.3, False,
                 t_process=time.perf_counter(), engine_factory=factory)
    assert result["correct"] is correct, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 6
    assert list(result["metrics"]) == ["output_tok_s", "setup_s"]
    assert list(result)[-1] == "checks"


def test_a_traced_run_reports_the_per_layer_metrics(tiny_cell):
    result = run("tiny.mix", 5, 1.0, True, t_process=time.perf_counter())
    assert result["correct"] is True
    assert set(result["metrics"]) == {"prefill_ms", "decode_tick_ms"}
    assert result["device"]["window_s"] > 0
    assert "idle_gaps" in result["breakdown"]


def test_the_cpu_is_refused():
    with pytest.raises(NoDevice):
        check_device(1)


def test_run_py_exits_2_without_a_result_off_a_tpu(capsys):
    from bench.run import main

    assert main(["--workload", "smollm-135m.decode", "--seed", "1",
                 "--seconds", "1", "--trace", "0"]) == 2
    assert capsys.readouterr().out == ""


def test_run_py_pins_the_allocator():
    import subprocess
    import sys

    from bench.run import ROOT

    done = subprocess.run(
        [sys.executable, "-c", "import bench.run; bench.run.pin_allocator()"],
        cwd=ROOT, capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr


def test_seeds_draw_the_same_amount_of_work(tiny_cell):
    from bench.traffic import Calls, Mix

    mix = Mix.load("tinymix")
    sizes = {tuple(sorted(len(p) for p in Calls(mix, 512, s).call(0)))
             for s in (1, 2, 2 ** 40)}
    assert len(sizes) == 1
    assert np.asarray(jnp.zeros(1)).shape == (1,)
