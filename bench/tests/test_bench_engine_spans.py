"""The readers of the engine's admission and tick-phase spans: on synthetic
spans, silent where the spans are absent (a program without them), and on
a traced run of a tiny cell on the CPU."""

import json
import time

import pytest

from bench.harness import run
from bench.observe import HostSpan, Observations, Request, read_metric


def observations(spans):
    return Observations(spec=None, peak=None, window=(0.0, 2.0),
                        requests=[Request(128, 4)], spans=spans)


def admit(t0, wait=None):
    attrs = {"prompt_len": 128}
    if wait is not None:
        attrs.update(queue_wait_s=wait, ttft_s=wait + 0.01)
    return HostSpan("engine.admit", t0, 0.01, attrs)


def tick(t0, dur, wait=None):
    spans = [HostSpan("engine.tick", t0, dur, {"tick": 0, "active": 4})]
    if wait is not None:
        spans += [HostSpan("engine.tick.launch", t0, 0.001, {}),
                  HostSpan("engine.tick.wait", t0 + 0.001, wait, {}),
                  HostSpan("engine.tick.fetch", t0 + 0.001 + wait, 0.001,
                           {})]
    return spans


def test_queue_wait_ms_is_the_mean_admission_wait():
    obs = observations([admit(0.0, 0.2), admit(0.3, 0.4),
                        *tick(0.5, 0.05, 0.04)])
    assert read_metric("queue_wait_ms", obs) == pytest.approx(300.0)


def test_tick_host_ms_is_the_tick_less_its_wait():
    obs = observations([admit(0.0, 0.2), *tick(0.3, 0.05, 0.04),
                        *tick(0.4, 0.07, 0.04)])
    assert read_metric("tick_host_ms", obs) == pytest.approx(20.0)


def test_tick_host_ms_leaves_out_a_tick_without_its_wait():
    obs = observations([*tick(0.3, 0.05, 0.04), *tick(0.4, 0.9)])
    assert read_metric("tick_host_ms", obs) == pytest.approx(10.0)


@pytest.mark.parametrize("metric", ["queue_wait_ms", "tick_host_ms"])
def test_silent_without_the_spans(metric):
    assert read_metric(metric, observations([])) is None
    # a program whose spans carry no phases or stamps: the parent's
    older = [admit(0.0), *tick(0.3, 0.05)]
    assert read_metric(metric, observations(older)) is None


def test_a_traced_run_reports_both_and_splits_the_tick(tiny_cell):
    bench = json.loads(tiny_cell.read_text())
    metric = {"better": "lower", "source": "program_span", "layer": "engine",
              "moves": "output_tok_s", "unit": "ms"}
    bench["per_layer"] += [dict(metric, name="queue_wait_ms"),
                           dict(metric, name="tick_host_ms")]
    tiny_cell.write_text(json.dumps(bench))
    result = run("tiny.mix", 2 ** 31 + 5, 1.0, True,
                 t_process=time.perf_counter())
    assert result["correct"] is True
    m = {k: v["value"] for k, v in result["metrics"].items()}
    assert m["queue_wait_ms"] > 0
    assert 0 < m["tick_host_ms"] < m["decode_tick_ms"]
    labels = [label for label, _ in result["breakdown"]["idle_gaps"]]
    assert any(label.startswith("engine.tick.") for label in labels)
