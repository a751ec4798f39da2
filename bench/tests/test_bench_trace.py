"""The reductions from a trace and spans to per-layer metrics, on a small
synthetic trace whose operations are named as a TPU trace names them."""

import collections

import pytest

from bench.devtrace import DeviceTrace, Op, attribute_gaps, reduce_profile
from bench.flops import gemm_bytes, gemm_flops, least_time_s
from bench.model import ModelSpec
from bench.observe import HostSpan, Observations, Request, read_metric
from bench.peaks import peak_for

V5E = peak_for("TPU v5 lite")
L = "{1,0:T(8,128)(2,1)}"               # in HBM
VMEM = "{1,0:T(8,128)(2,1)S(1)}"        # staged in the core's VMEM


def gemm_op(t0, dur, m, k, n, name="checkpoint.47", a=L, b=L, c=L):
    return Op(0, f"%{name} = bf16[1,{m},{n}]{c} custom-call(bf16[{m},{k}]{a} "
                 f"%pad.1, bf16[{k},{n}]{b} %pad.2), custom_call_target="
                 f"\"tpu_custom_call\", operand_layout_constraints="
                 f"{{bf16[{m},{k}]{{1,0}}, bf16[{k},{n}]{{1,0}}}}", t0, dur)


def copy_op(t0, dur):
    return Op(0, f"%copy.3 = bf16[30,64,2048,3,64]{L} copy(bf16[30,64,2048,"
                 f"3,64]{L} %args_0_.1)", t0, dur)


@pytest.fixture
def obs():
    spec = ModelSpec.load("smollm-135m")
    spans = [HostSpan("engine.admit", 0.00, 0.20, {"prompt_len": 128}),
             HostSpan("engine.prefill", 0.01, 0.18, {"prompt_len": 128}),
             HostSpan("engine.tick", 0.30, 0.20, {"tick": 0}),
             HostSpan("engine.tick", 0.60, 0.30, {"tick": 1})]
    ops = [gemm_op(0.05, 0.01, 128, 640, 1536),       # prefill: gate/up
           copy_op(0.06, 0.04),
           gemm_op(0.35, 0.02, 64, 1536, 640),        # tick: down
           gemm_op(0.40, 0.01, 64, 640, 256),         # tick: k or v
           gemm_op(0.70, 0.05, 64, 640, 640),         # tick: q or o
           gemm_op(0.95, 0.01, 64, 640, 640)]         # outside every span
    trace = DeviceTrace(window=(0.0, 1.0), ops=ops, chips=1)
    return Observations(spec=spec, peak=V5E, window=(0.0, 2.0),
                        requests=[Request(128, 4), Request(256, 4)],
                        spans=spans, trace=trace)


def lt(m, n, k):
    return least_time_s(gemm_flops(m, n, k), gemm_bytes(m, n, k), V5E)


def test_gemm_roofline_counts_logical_work_of_matched_ops(obs):
    want = (lt(128, 1536, 576) + lt(64, 576, 1536) + lt(64, 192, 576)
            + lt(64, 576, 576)) / (0.01 + 0.02 + 0.01 + 0.05)
    assert read_metric("gemm_roofline", obs) == pytest.approx(100 * want)


# smollm-135m.decode's gate projection in a decode tick, as the chip's trace
# names it: the compiler staged A, the padded weight and C in VMEM, so the
# call itself moves no HBM bytes (the weight's dynamic-slice and pad read it
# from HBM before the call).  2317 such calls took 1.741 ms on a TPU v5e.
SMOLLM_GATE = (
    "%checkpoint.46 = bf16[1,64,1536]{2,1,0:T(8,128)(2,1)S(1)} custom-call("
    "bf16[64,640]{1,0:T(8,128)(2,1)S(1)} %pad.139, bf16[640,1536]{1,0:T(8,"
    "128)(2,1)S(1)} %pad.140), custom_call_target=\"tpu_custom_call\", "
    "operand_layout_constraints={bf16[64,640]{1,0}, bf16[640,1536]{1,0}}, "
    "frontend_attributes={kernel_metadata={}}")


def test_gemm_roofline_counts_no_hbm_bytes_for_arrays_in_vmem(obs):
    dur = 1.741e-3 / 2317
    obs.trace.ops = [Op(0, SMOLLM_GATE, 0.31 + 1e-5 * i, dur)
                     for i in range(20)]
    got = read_metric("gemm_roofline", obs)
    # compute-bound: 2 * 64 * 1536 * 576 FLOP at 197 TFLOP/s over the call
    assert got == pytest.approx(100 * gemm_flops(64, 1536, 576) / 197e12
                                / dur)
    assert 70 < got < 100
    # counted with A, B and C read from and written to HBM, as before, the
    # same calls read 331%: more than the chip can do
    assert 100 * lt(64, 1536, 576) / dur == pytest.approx(331, abs=1)


def test_gemm_roofline_counts_only_the_arrays_in_hbm(obs):
    """A qwen3-14b decode GEMM whose activations sit in VMEM and whose
    weight streams from HBM is bound by reading the weight alone."""
    obs.spec = ModelSpec.load("qwen3-14b")
    m, n, k = obs.spec.slots, 17408, 5120
    obs.trace.ops = [gemm_op(0.35, 1e-4, m, k, n, a=VMEM, c=VMEM)]
    want = max(gemm_flops(m, n, k) / 197e12, 2 * k * n / 819e9)
    assert read_metric("gemm_roofline", obs) == pytest.approx(100 * want
                                                              / 1e-4)


def test_memory_spaces_are_read_from_the_layouts():
    op = gemm_op(0, 0, 16, 5120, 17408, a=VMEM, c=VMEM)
    assert [x.space for x in op.operands()] == [1, 0]
    assert [x.space for x in op.results()] == [1]
    assert op.operands()[1].dims == (5120, 17408)


def test_gemm_roofline_is_silent_without_gemms(obs):
    obs.trace.ops = [copy_op(0.1, 0.1)]
    assert read_metric("gemm_roofline", obs) is None
    obs.trace = None
    assert read_metric("gemm_roofline", obs) is None


def test_device_idle_is_one_minus_the_union(obs):
    # busy: [0.05,0.10] + [0.35,0.37] + [0.40,0.41] + [0.70,0.75]
    #       + [0.95,0.96] = 0.05 + 0.02 + 0.01 + 0.05 + 0.01
    assert obs.trace.busy_s() == pytest.approx(0.14)
    assert read_metric("device_idle", obs) == pytest.approx(86.0)


def test_span_means(obs):
    assert read_metric("prefill_ms", obs) == pytest.approx(180.0)
    assert read_metric("decode_tick_ms", obs) == pytest.approx(250.0)
    obs.spans = []
    assert read_metric("prefill_ms", obs) is None


def test_mfu_counts_live_tokens_over_the_window(obs):
    flops = (obs.spec.shape.request_model_flops(128, 3)
             + obs.spec.shape.request_model_flops(256, 3))
    assert read_metric("mfu", obs) == pytest.approx(
        100 * flops / (2.0 * 197e12))


def test_idle_gaps_go_to_the_host_span_around_them(obs):
    gaps = obs.trace.idle_gaps(0)
    got = dict(attribute_gaps(gaps, obs.spans, obs.trace.ops))
    assert got["host outside engine spans"] == pytest.approx(
        0.10 + 0.10 + 0.09)
    assert got["engine.prefill: host before its device work"] == \
        pytest.approx(0.04)
    assert sum(got.values()) == pytest.approx(1.0 - 0.14)


Plane = collections.namedtuple("Plane", "name lines")
Line = collections.namedtuple("Line", "name events")
Event = collections.namedtuple("Event", "name start_ns duration_ns")


def test_reduce_profile_maps_the_trace_clock_onto_the_host_clock():
    gemm = gemm_op(0, 0, 64, 640, 640).name
    planes = [
        Plane("/host:CPU", [Line("python", [
            Event("bench.window", 1_000_000, 3_000_000)])]),
        Plane("/device:TPU:0", [
            Line("XLA Modules", [Event("jit_fn(1)", 1_500_000, 900_000)]),
            Line("XLA Ops", [
                Event("%while.2 = (s32[]{:T(128)}, bf16[64,1,576]) while("
                      "%tuple.1), condition=%c", 1_500_000, 900_000),
                Event(gemm, 1_600_000, 100_000)])]),
        Plane("/device:TPU:0 SparseCore", [Line("XLA Ops", [
            Event(gemm, 1_600_000, 100_000)])]),
    ]
    t = reduce_profile(planes, anchor_pc=50.0)
    assert t.window == pytest.approx((50.0, 50.003))
    assert t.chips == 1
    assert len(t.ops) == 1                      # the while body only
    assert t.ops[0].t0 == pytest.approx(50.0006)
    assert t.ops[0].dur == pytest.approx(1e-4)
    assert [x[:2] for x in t.ops[0].operands()] == [("bf16", (64, 640)),
                                                    ("bf16", (640, 640))]


def test_reduce_profile_needs_the_annotation():
    with pytest.raises(RuntimeError):
        reduce_profile([Plane("/device:TPU:0", [])], anchor_pc=0.0)
