"""The sparse-expert, mixed-window architecture (``moe_swa``): it joins the
benchmark as files of its own, its weights are the tree the program lays
out, its counts are the hand count, and its two readers reduce a synthetic
trace and spans as a TPU run gives them."""

import json
import time

import jax
import numpy as np
import pytest

from bench.devtrace import DeviceTrace, Op
from bench.flops import least_time_s
from bench.model import ModelSpec, seed_key
from bench.observe import HostSpan, Observations, read_metric
from bench.peaks import peak_for

SLIDING, FULL = "sliding_attention", "full_attention"
TINY_MOE = {
    "name": "tiny", "source": "test", "program": "mellum2-12b-a2.5b",
    "reference": "moe_swa", "architecture": {"kind": "moe_swa"},
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 8, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "num_hidden_layers": 8,
    "layer_types": [SLIDING] * 3 + [FULL] + [SLIDING] * 3 + [FULL],
    "mlp_layer_types": ["sparse"] * 8, "sliding_window": 8,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 4,
               "original_max_position_embeddings": 16, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.138629436111989},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "rms_norm_eps": 1e-06, "tie_word_embeddings": False, "vocab_size": 256,
    "config": {"torch_dtype": "bfloat16"}, "serve": {"slots": 4, "max_len": 64}}
V5E = peak_for("TPU v5 lite")


def test_weight_tree_matches_init_params():
    from repro.models import init_params

    spec = ModelSpec.from_dict(TINY_MOE)
    want = jax.eval_shape(lambda k: init_params(spec.program_config(), k),
                          jax.random.PRNGKey(0))
    got = jax.eval_shape(spec.make_weights, seed_key(1))
    assert jax.tree_util.tree_structure(got) == \
        jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    assert got["layers"]["pos0"]["moe"]["w_gate"].shape == (2, 8, 64, 32)
    assert got["layers"]["pos0"]["moe"]["w_down"].shape == (2, 8, 32, 64)


def test_published_configuration_reads_as_published():
    spec = ModelSpec.load("mellum2-12b-a2.5b")
    s, cfg = spec.shape, spec.program_config()
    assert (s.layers, s.d_model, s.n_heads, s.n_kv, s.head_dim) == \
        (8, 2304, 32, 4, 128)
    assert (s.n_experts, s.top_k, s.expert_ff, s.vocab, s.window) == \
        (64, 8, 896, 98304, 1024)
    assert [m for m, _ in cfg.pattern] == ["attn_window"] * 3 + ["attn"]
    assert cfg.n_repeats == 2 and not cfg.tie_embeddings
    assert cfg.rope_yarn.factor == 16 and cfg.rope_theta == 500000
    assert spec.raw["published"]["num_hidden_layers"] == 28
    assert sorted(spec.raw["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "mlp_layer_types"])


def test_projections_leave_the_experts_out():
    s = ModelSpec.load("mellum2-12b-a2.5b").shape
    assert s.projections() == [("q", 4096, 2304), ("k", 512, 2304),
                               ("v", 512, 2304), ("o", 2304, 4096)]
    assert s.expert_gemms() == [("gate", 896, 2304), ("up", 896, 2304),
                                ("down", 2304, 896)]


def test_request_flops_match_a_hand_count():
    s = ModelSpec.load("mellum2-12b-a2.5b").shape
    # one token, one layer: q, k, v, o; the router; 8 experts of 3 GEMMs
    per_token = (2304 * 4096 * 2 + 2304 * 512 * 2 + 2304 * 64
                 + 8 * 3 * 2304 * 896)
    assert per_token == 70926336
    tokens = 4096 + 63
    per_pos = 4 * 32 * 128                     # QK^T and PV of 32 heads
    full = tokens * (tokens + 1) // 2           # position p sees p + 1
    window = 1024 * 1025 // 2 + (tokens - 1024) * 1024
    want = (2 * 8 * per_token * tokens + per_pos * (2 * full + 6 * window)
            + 2 * 2304 * 98304 * 64)
    assert s.request_model_flops(4096, 63) == want
    # shorter than the window, a window layer sees what a full one sees
    short = 2 * 8 * per_token * 100 + per_pos * 8 * (100 * 101 // 2) \
        + 2 * 2304 * 98304 * 1
    assert s.request_model_flops(100, 0) == short


L = "{1,0:T(8,128)(2,1)}"                   # in HBM
VMEM = "{1,0:T(8,128)(2,1)S(1)}"            # staged in the core's VMEM
L3 = "{2,1,0:T(8,128)(2,1)}"


def gmm_op(t0, dur, m, k, n, a=L, c=L, groups=128):
    """The Pallas grouped GEMM as a TPU trace names it: the group
    metadata first, then the rows (m, k) and the stacked weights."""
    return Op(0, f"%gmm.12 = bf16[{m},{n}]{c} custom-call(s32[65]{{0}} "
                 f"%dynamic_slice.20, s32[256]{{0}} %dynamic_slice.21, "
                 f"s32[1]{{0}} %get-tuple-element.4, bf16[{m},{k}]{a} "
                 f"%gather.3, bf16[{groups},{k},{n}]{L3} %w.1), "
                 f"custom_call_target=\"tpu_custom_call\"", t0, dur)


def dense_op(t0, dur, m, k, n):
    return Op(0, f"%checkpoint.47 = bf16[1,{m},{n}]{L} custom-call("
                 f"bf16[{m},{k}]{L} %pad.1, bf16[{k},{n}]{L} %pad.2), "
                 f"custom_call_target=\"tpu_custom_call\"", t0, dur)


@pytest.fixture
def obs():
    spec = ModelSpec.load("mellum2-12b-a2.5b")
    attrs = {"prompt_len": 4096, "moe_rows": 8 * 4096 * 8,
             "experts_touched": 64.0, "expert_rows_max": 1024}
    tick = {"tick": 0, "moe_rows": 16 * 8 * 8, "experts_touched": 50.0,
            "expert_rows_max": 6}
    spans = [HostSpan("engine.prefill", 0.0, 0.2, attrs),
             HostSpan("engine.tick", 0.3, 0.1, tick),
             HostSpan("engine.tick", 0.5, 0.1, {"tick": 1})]
    ops = [gmm_op(0.01, 4e-3, 32768, 2304, 896),          # prefill: gate
           gmm_op(0.02, 4e-3, 32768, 896, 2304, a=VMEM),  # prefill: down
           dense_op(0.03, 1e-3, 4096, 2304, 4096),        # q: not an expert
           gmm_op(0.31, 1e-3, 128, 2304, 896, a=VMEM, c=VMEM),   # tick
           gmm_op(0.51, 1e-3, 128, 2304, 896)]            # no counters
    trace = DeviceTrace(window=(0.0, 1.0), ops=ops, chips=1)
    return Observations(spec=spec, peak=V5E, window=(0.0, 1.0), requests=[],
                        spans=spans, trace=trace)


def test_expert_gemm_roofline_reads_the_grouped_gemms(obs):
    rows_p, rows_t = 4096 * 8, 16 * 8
    want = (
        # prefill gate: all 64 experts' weights, A and C in HBM
        least_time_s(2 * rows_p * 896 * 2304,
                     2 * (64 * 2304 * 896 + rows_p * 2304 + rows_p * 896),
                     V5E)
        # prefill down: A in VMEM moves no HBM bytes
        + least_time_s(2 * rows_p * 2304 * 896,
                       2 * (64 * 896 * 2304 + rows_p * 2304), V5E)
        # the tick's gate: 50 experts touched, A and C in VMEM
        + least_time_s(2 * rows_t * 896 * 2304, 2 * 50 * 2304 * 896, V5E))
    got = read_metric("expert_gemm_roofline", obs)
    assert got == pytest.approx(100 * want / (4e-3 + 4e-3 + 1e-3))
    assert 0 < got < 100


def test_expert_load_max_is_the_largest_group_over_an_even_share(obs):
    # prefill: 1024 / (262144 / 512); tick: 6 / (1024 / 512)
    assert read_metric("expert_load_max", obs) == pytest.approx(
        (1024 / 512 + 6 / 2) / 2)


def test_expert_readers_are_silent_for_a_dense_architecture(obs):
    obs.spec = ModelSpec.load("qwen3-14b")
    assert read_metric("expert_gemm_roofline", obs) is None
    assert read_metric("expert_load_max", obs) is None
    obs.spec = ModelSpec.load("mellum2-12b-a2.5b")
    obs.spans = [s for s in obs.spans if "moe_rows" not in s.attrs]
    assert read_metric("expert_gemm_roofline", obs) is None
    assert read_metric("expert_load_max", obs) is None


def test_gemm_roofline_leaves_the_expert_gemms_out(obs):
    obs.trace.ops = [o for o in obs.trace.ops if "gmm" in o.name]
    assert read_metric("gemm_roofline", obs) is None


def test_moe_swa_serves_a_tiny_cell_through_the_harness(tiny_cell):
    """The architecture named by kind in its file, with the two readers,
    through the harness's whole run: correct, with the routing counters
    read from the engine's spans."""
    from bench.harness import run
    import bench.model

    (bench.model.CONFIG_DIR / "tiny.json").write_text(json.dumps(TINY_MOE))
    bench_json = json.loads(tiny_cell.read_text())
    for name in ("expert_gemm_roofline", "expert_load_max"):
        bench_json["per_layer"].append({
            "name": name, "unit": "%", "better": "higher",
            "source": "program_span", "layer": "engine",
            "moves": "output_tok_s"})
    tiny_cell.write_text(json.dumps(bench_json))
    result = run("tiny.mix", 2 ** 31 + 11, 0.3, True,
                 t_process=time.perf_counter())
    assert result["correct"] is True, result["checks"]
    load = result["metrics"]["expert_load_max"]["value"]
    # an expert takes at most every token: E / top_k even shares
    assert 1.0 <= load <= 8 / 2
    assert "expert_gemm_roofline" not in result["metrics"]   # no TPU ops
    assert np.isfinite(result["metrics"]["prefill_ms"]["value"])
