"""A tiny cell on the CPU: its configuration, mix and limits in a
temporary directory that the benchmark's loaders read instead of their own."""

import json

import pytest

TINY_CONFIG = {
    "name": "tiny", "source": "test", "program": "qwen3-14b",
    "reference": "dense_gqa", "architecture": {"kind": "dense_gqa", "qk_norm": True},
    "config": {"hidden_size": 64, "intermediate_size": 128,
               "num_attention_heads": 4, "num_key_value_heads": 2,
               "head_dim": 16, "num_hidden_layers": 2, "rms_norm_eps": 1e-6,
               "rope_theta": 1000000, "tie_word_embeddings": True,
               "torch_dtype": "bfloat16", "vocab_size": 512},
    "serve": {"slots": 4, "max_len": 64}}
TINY_MIX = {"loop": "closed", "requests_per_call": 6, "max_new": 8,
            "prompt_len": {"median": 16, "sigma": 0.5, "min": 8, "max": 32,
                           "grid": 8}}
METRIC = {"better": "lower", "source": "program_span", "layer": "engine",
          "moves": "output_tok_s"}
TINY_BENCHMARK = {
    "workloads": [{"name": "tiny.mix", "config": "tiny", "traffic": "tinymix",
                   "chips": 1, "why": "test"}],
    "end_to_end": [
        {"name": "output_tok_s", "unit": "tokens/s", "better": "higher",
         "bound": 0.03, "source": "host_clock"},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
         "source": "host_clock"}],
    "per_layer": [dict(METRIC, name="prefill_ms", unit="ms"),
                  dict(METRIC, name="decode_tick_ms", unit="ms")]}


@pytest.fixture
def tiny_cell(tmp_path, monkeypatch):
    """Path of a BENCHMARK.json whose one cell, ``tiny.mix``, is tiny; the
    harness reads it, and takes the CPU that it finds for its device."""
    import jax

    import bench.correctness
    import bench.harness
    import bench.model
    import bench.peaks
    import bench.traffic

    for sub, name, body in [("configs", "tiny", TINY_CONFIG),
                            ("traffic", "tinymix", TINY_MIX),
                            ("cells", "tiny.mix", {"max_logit_gap": 0.05,
                                                   "sample_tokens": 48})]:
        (tmp_path / sub).mkdir()
        (tmp_path / sub / f"{name}.json").write_text(json.dumps(body))
    monkeypatch.setattr(bench.model, "CONFIG_DIR", tmp_path / "configs")
    monkeypatch.setattr(bench.traffic, "TRAFFIC_DIR", tmp_path / "traffic")
    monkeypatch.setattr(bench.correctness, "CELL_DIR", tmp_path / "cells")
    # no persistent compile cache for CPU test programs
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jaxcache"))
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(TINY_BENCHMARK))
    monkeypatch.setattr(bench.harness, "BENCHMARK", path)
    cpu = jax.devices()[0]
    monkeypatch.setattr(bench.harness, "check_device", lambda chips: {
        "platform": cpu.platform, "kind": cpu.device_kind, "count": 1})
    monkeypatch.setattr(bench.peaks, "peak_for", lambda kind: None)
    return path
