"""Serve smollm-135m once on a TPU through the compiled Pallas kernels, and
check what comes out.

Run from the repo root on a machine with one TPU:  python chip_smoke.py

Every phase runs in this one process (a chip belongs to one process), prints
one line, and the script exits non-zero at the first failure:

  device   JAX's first device must be a TPU
  kernels  GEMM, flash attention, conv and SSD, each compiled
           (interpret=False) at a real width, against its kernels/ref.py
           oracle in f32
  measure  wall-clock timing of a few legal GEMM configs for every GEMM
           shape the served decode and prefill programs run, written as
           tuning records into a store this run creates
  serve    Engine with that store: 8 requests x 16 tokens, the decode
           program holds Pallas kernels, every served GEMM shape resolved
           from the store's exact tier
  logits   the last-position prefill logits of 2 prompts against the same
           prefill with the GEMMs on jnp.dot

The model is smollm-135m at its published widths with random weights from
seed 0.  Times printed here are smoke-run readings, not benchmark numbers.
The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}
"""

from __future__ import annotations

import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

SEED = 0
SLOTS, MAX_LEN = 4, 256
N_REQUESTS, PROMPT_LEN, MAX_NEW = 8, 32, 16
CONFIGS_PER_SHAPE = 3
KERNEL_TOL = 2e-2          # max |got - ref| / max |ref|, bf16 operands
# ||kernel - jnp.dot||_2 / ||jnp.dot||_2.  One GEMM's two paths differ in
# ~1e-4 of their bf16 outputs by an ulp, and the random-weight stack
# amplifies that with depth: the CPU rehearsal (kernels interpreted) gave
# 4.5e-3 at 2 layers, 1.0e-2 at 8 and 1.6e-2 at 30 (width cut to 192; full
# width tracked the cut one within 10% at 2 and 8 layers).  A wrong kernel
# lands near 1.
LOGIT_TOL = 3e-2
STORE = ROOT / "chiprun_out" / "chip_smoke" / "tunedb.jsonl"


def say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def fail(phase: str, msg: str) -> None:
    say(phase, f"FAIL: {msg}")
    sys.exit(1)


def check_device() -> dict:
    dev = jax.devices()[0]
    found = {"platform": dev.platform, "kind": dev.device_kind,
             "count": len(jax.devices())}
    if dev.platform != "tpu":
        fail("device", f"JAX found no TPU (first device: {found})")
    say("device", f"platform={found['platform']} kind={found['kind']} "
                  f"count={found['count']}")
    return found


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    if not np.isfinite(got).all():
        return float("inf")
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-6))


def check_kernels() -> None:
    """Each kernel compiled, bf16 operands, against its f32 oracle."""
    from repro.kernels import ops, ref

    rng = np.random.default_rng(SEED)
    bf16 = lambda *shape, scale=1.0: jnp.asarray(
        rng.normal(size=shape) * scale, jnp.bfloat16)
    f32 = lambda x: x.astype(jnp.float32)

    def oracle(fn, *args, **kw):
        # f32 operands at full precision; the kernels run outside this
        # context (Mosaic refuses an f32 contract precision on bf16 dots)
        with jax.default_matmul_precision("highest"):
            return fn(*(f32(x) for x in args), **kw)

    def gemm(M, K, N):
        a, b = bf16(M, K), bf16(K, N, scale=K ** -0.5)
        return ops.matmul(a, b, interpret=False), oracle(ref.matmul_ref, a, b)

    def attention(Lq, Lkv, q_offset):
        q, k, v = bf16(1, 9, Lq, 64), bf16(1, 3, Lkv, 64), bf16(1, 3, Lkv, 64)
        got = ops.flash_attention(q, k, v, causal=True, q_offset=q_offset,
                                  interpret=False)
        return got, oracle(ref.attention_ref, q, k, v, causal=True,
                           q_offset=q_offset)

    def conv():
        i, f = bf16(8, 56, 56, 128), bf16(3, 3, 128, 128, scale=(9 * 128) ** -0.5)
        return ops.conv2d(i, f, interpret=False), oracle(ref.conv2d_ref, i, f)

    def ssd():
        B, L, H, P, S = 1, 1024, 8, 64, 64
        x, bm, cm = bf16(B, L, H, P), bf16(B, L, S), bf16(B, L, S)
        dt = jnp.asarray(rng.uniform(0.01, 0.1, (B, L, H)), jnp.bfloat16)
        a = -jnp.asarray(rng.uniform(0.5, 2.0, (H,)), jnp.float32)
        got = ops.ssd_scan(x, dt, a, bm, cm, interpret=False)
        return got, oracle(ref.ssd_ref, x, dt, a, bm, cm)

    cases = [("gemm decode M=4 K=576 N=1536", lambda: gemm(4, 576, 1536)),
             ("gemm prefill M=32 K=1536 N=576", lambda: gemm(32, 1536, 576)),
             ("attention prefill Lq=Lkv=256", lambda: attention(256, 256, 0)),
             ("attention decode Lq=1 Lkv=256 q_offset=100",
              lambda: attention(1, 256, 100)),
             ("conv N=8 56x56 C=K=128 3x3", conv),
             ("ssd L=1024 H=8 P=S=64", ssd)]
    for name, run in cases:
        got, want = run()
        err = rel_err(got, want)
        say("kernels", f"{name}: rel err {err:.3e}")
        if not err < KERNEL_TOL:
            fail("kernels", f"{name}: rel err {err:.3e} >= {KERNEL_TOL}")


def served_gemm_shapes(cfg, params) -> list:
    """Every distinct GEMM shape the engine's decode step and a
    PROMPT_LEN-token prefill record in telemetry (traced, not run)."""
    from repro.models import decode_step, init_cache, prefill
    from repro.tunedb.telemetry import get_telemetry

    def decode(p):
        return decode_step(p, cfg, jnp.zeros((SLOTS, 1), jnp.int32),
                           init_cache(cfg, SLOTS, MAX_LEN),
                           jnp.zeros((SLOTS,), jnp.int32))

    def prefill_one(p):
        return prefill(p, cfg, {"tokens": jnp.zeros((1, PROMPT_LEN),
                                                    jnp.int32)},
                       init_cache(cfg, 1, MAX_LEN))

    shapes = {}
    for step in (decode, prefill_one):
        with get_telemetry().capture() as cap:
            jax.eval_shape(step, params)
        for space, inputs in cap.shapes:
            if space == "gemm":
                shapes[tuple(sorted(inputs.items()))] = inputs
    return list(shapes.values())


def measure_into_store(shapes, backend_name: str, measure) -> dict:
    """Time CONFIGS_PER_SHAPE legal configs per shape; every timing is a
    training sample in a fresh store and each shape's fastest config its
    serving record.  Returns {shape key: served config}."""
    from repro.core.space import GEMM_SPACE
    from repro.tunedb import RecordStore, TuneRecord
    from repro.tunedb.store import SAMPLE_SOURCE, shape_key

    STORE.parent.mkdir(parents=True, exist_ok=True)
    if STORE.exists():
        STORE.unlink()
    store = RecordStore.open(STORE)
    rng = np.random.default_rng(SEED)
    winners = {}
    for inputs in shapes:
        # f32 accumulation, no split-K: the configs whose numerics the
        # logits check can hold to a tight bound
        legal = [c for c in GEMM_SPACE.enumerate_legal(inputs)
                 if c["acc32"] == 1 and c["k_split"] == 1]
        picks = [legal[i] for i in rng.choice(len(legal), CONFIGS_PER_SHAPE,
                                              replace=False)]
        timed = []
        for cfg in picks:
            tflops = float(measure("gemm", cfg, inputs))
            timed.append((tflops, cfg))
            store.add(TuneRecord(space="gemm", inputs=inputs, config=cfg,
                                 tflops=tflops, backend=backend_name,
                                 source=SAMPLE_SOURCE))
            say("measure", f"M={inputs['M']} N={inputs['N']} K={inputs['K']} "
                           f"bm={cfg['bm']} bn={cfg['bn']} bk={cfg['bk']} "
                           f"k_unroll={cfg['k_unroll']} order={cfg['order']}: "
                           f"{tflops:.4f} TFLOP/s (smoke-run reading, not a "
                           f"benchmark number)")
        tflops, best = max(timed, key=lambda t: t[0])
        store.add(TuneRecord(space="gemm", inputs=inputs, config=best,
                             tflops=tflops, backend=backend_name,
                             source="tuner"))
        winners[shape_key(inputs)] = best
    say("measure", f"{len(shapes)} GEMM shapes x {CONFIGS_PER_SHAPE} configs "
                   f"timed; {len(store.records())} serving records in {STORE}")
    return winners


def serve(cfg, params, winners, prompts) -> None:
    from repro.kernels import dispatch
    from repro.serve import Engine, ServeConfig
    from repro.tunedb.store import serving_state, shape_key

    resolved = []
    real_resolve = dispatch._resolve_cfg

    def recording_resolve(space, inputs):
        got = real_resolve(space, inputs)
        resolved.append((space, dict(inputs), got[0], got[1]))
        return got

    dispatch._resolve_cfg = recording_resolve
    try:
        eng = Engine(cfg, params, ServeConfig(max_len=MAX_LEN, slots=SLOTS,
                                              tunedb=str(STORE)))
        t0 = time.perf_counter()
        outs = eng.generate(prompts, max_new=MAX_NEW)
        dt = time.perf_counter() - t0
    finally:
        dispatch._resolve_cfg = real_resolve

    bad = [o for o in outs
           if len(o) != MAX_NEW or not all(0 <= t < cfg.vocab for t in o)]
    if len(outs) != N_REQUESTS or bad:
        fail("serve", f"expected {N_REQUESTS} x {MAX_NEW} token ids in "
                      f"[0, {cfg.vocab}); got lengths {[len(o) for o in outs]}")
    total = sum(len(o) for o in outs)
    say("serve", f"{len(outs)} requests x {MAX_NEW} tokens = {total} tokens, "
                 f"{eng.ticks} decode ticks, {dt:.2f}s with compiles "
                 f"(smoke-run reading, not a benchmark number)")

    last = jnp.zeros((SLOTS, 1), jnp.int32)
    idx = jnp.zeros((SLOTS,), jnp.int32)
    text = eng._decode.lower(eng.params, last, eng.cache, idx).compile(
        ).as_text()
    n_kernels = text.count("tpu_custom_call")
    if not n_kernels:
        fail("serve", "the compiled decode program holds no tpu_custom_call")
    say("serve", f"compiled decode program: {n_kernels} tpu_custom_call "
                 "mentions")

    plan = serving_state().plan
    gemm = [(inp, c, tier) for space, inp, c, tier in resolved
            if space == "gemm"]
    if not gemm:
        fail("serve", "no GEMM resolved through dispatch")
    for inputs, got, tier in gemm:
        key = shape_key(inputs)
        origin = plan.lookup("gemm", key)[1] if tier == "plan" else tier
        if origin != "exact" or got != winners.get(key):
            fail("serve", f"GEMM {inputs} resolved from tier {tier}/{origin} "
                          f"to {got}, not the store's exact record")
    say("serve", f"{len(gemm)} GEMM resolutions over "
                 f"{len({shape_key(i) for i, _, _ in gemm})} shapes, all from "
                 "the store's exact tier")


def check_logits(cfg, params, prompts) -> None:
    """Last-position prefill logits, GEMMs on the Pallas kernels against
    the same prefill with the GEMMs on jnp.dot."""
    from repro.kernels import dispatch
    from repro.models import init_cache, prefill

    def last_logits(tokens):
        # a fresh jit per call: dispatch picks its path while tracing
        step = jax.jit(lambda p, t: prefill(
            p, cfg, {"tokens": t}, init_cache(cfg, 1, MAX_LEN))[0])
        return np.asarray(step(params, tokens), np.float32)

    real_on_tpu = dispatch.on_tpu
    for n, prompt in enumerate(prompts[:2]):
        tokens = jnp.asarray(prompt[None], jnp.int32)
        kernel = last_logits(tokens)
        dispatch.on_tpu = lambda: False
        try:
            dot = last_logits(tokens)
        finally:
            dispatch.on_tpu = real_on_tpu
        err = float(np.linalg.norm(kernel - dot) / np.linalg.norm(dot))
        same = int(kernel.argmax() == dot.argmax())
        say("logits", f"prompt {n}: ||kernel - jnp.dot||/||jnp.dot|| = "
                      f"{err:.3e}, argmax agrees: {bool(same)}")
        if not (np.isfinite(kernel).all() and err <= LOGIT_TOL):
            fail("logits", f"prompt {n}: rel err {err:.3e} > {LOGIT_TOL}")


def main() -> int:
    device = check_device()
    from repro.configs.smollm_135m import CONFIG as cfg
    from repro.core.backend import WallClockBackend
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import init_params

    say("setup", f"compile cache: {enable_compile_cache()}")
    check_kernels()
    params = init_params(cfg, jax.random.PRNGKey(SEED))
    say("setup", f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
                 f"{cfg.n_heads} heads / {cfg.n_kv} kv, d_ff {cfg.d_ff}, "
                 f"vocab {cfg.vocab}, random weights from seed {SEED}")
    shapes = served_gemm_shapes(cfg, params)
    winners = measure_into_store(
        shapes, f"wallclock-{device['kind']}",
        WallClockBackend(warmup=1, iters=20).measure)
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab, PROMPT_LEN)
               for _ in range(N_REQUESTS)]
    serve(cfg, params, winners, prompts)
    check_logits(cfg, params, prompts)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
