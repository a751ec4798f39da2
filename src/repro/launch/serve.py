"""Serving launcher: batched generation through the continuous-batching
engine.  ``python -m repro.launch.serve --arch smollm-135m --smoke``"""

import argparse

import jax
import numpy as np

from repro.configs import ARCH_NAMES, get_config, smoke_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params
from repro.serve import Engine, ServeConfig


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCH_NAMES, default="smollm-135m")
    p.add_argument("--smoke", action="store_true")
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--max-new", type=int, default=32)
    p.add_argument("--max-len", type=int, default=256)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--tunedb", default=None,
                   help="warm-start kernel dispatch from this record store")
    p.add_argument("--tunedb-backend", default=None,
                   help="pin dispatch to one backend fingerprint")
    p.add_argument("--admission", choices=["fifo", "store"], default="fifo",
                   help="batch admission policy: 'store' prefers pending "
                        "requests whose prefill shapes hit the frozen "
                        "dispatch plan and groups equal prompt lengths")
    p.add_argument("--retune", action="store_true",
                   help="enable in-process continuous retuning "
                        "(drift-triggered sessions + model hot-swap)")
    p.add_argument("--retune-interval", type=int, default=64,
                   help="decode ticks between retune-controller polls")
    p.add_argument("--retune-async", action="store_true",
                   help="run triggered retune epochs on a background "
                        "thread: polls submit and return, the swap lands "
                        "when the session+retrain completes")
    p.add_argument("--retune-fleet", default=None,
                   help="fleet directory to publish drift-triggered plans "
                        "to (run `python -m repro.tunedb fleet worker` "
                        "processes against it); implies --retune-async")
    p.add_argument("--retune-cooldown-ticks", type=int, default=0,
                   help="decode ticks a retune blocks the next trigger for")
    p.add_argument("--retune-max-sessions", type=int, default=0,
                   help="retune sessions allowed per --retune-window "
                        "seconds (0 = unlimited)")
    p.add_argument("--retune-window", type=float, default=600.0)
    p.add_argument("--retune-min-gain", type=float, default=0.0,
                   help="skip epochs whose projected gain over the "
                        "nearest-record tier is below this fraction")
    p.add_argument("--retune-sentry", type=float, default=None,
                   help="regression-sentry noise margin gating each "
                        "retune's serving swap (omit to disable)")
    p.add_argument("--plan-dir", default=None,
                   help="cold-start from this persisted plan artifact "
                        "(`tunedb plan export`) instead of compiling one "
                        "at install time")
    p.add_argument("--follow", default=None,
                   help="plan registry directory to follow: each published "
                        "generation is pulled, digest-verified, and "
                        "hot-swapped into serving")
    p.add_argument("--follow-interval", type=float, default=2.0,
                   help="seconds between plan-registry polls")
    p.add_argument("--retune-publish", default=None,
                   help="plan registry directory each successful retune "
                        "publishes its compiled plan to")
    p.add_argument("--telemetry-export", type=float, default=0.0,
                   help="with --retune-fleet: export this engine's shape "
                        "telemetry to the fleet bus every N seconds and "
                        "retune off the aggregated fleet-global view "
                        "(0 = process-local telemetry)")
    p.add_argument("--router", choices=["affinity", "round_robin", "random"],
                   default=None,
                   help="request-router policy: 'affinity' routes each "
                        "request to the replica whose dispatch plan covers "
                        "its shapes (load-bounded, with a no-starvation "
                        "escape); omit to disable routing")
    p.add_argument("--status-port", type=int, default=None,
                   help="serve /metrics, /status, /plan and /trace from "
                        "inside the engine on this port (0 = ephemeral)")
    p.add_argument("--trace-sample", type=float, default=0.0,
                   help="request-trace sampling rate (0 = tracing off, "
                        "1.0 = every trace root); spans export via /trace, "
                        "--trace-out, and `tunedb trace`")
    p.add_argument("--trace-out", default=None,
                   help="write the run's spans as Chrome trace-event JSON "
                        "here after generation (open in Perfetto)")
    p.add_argument("--request-deadline", type=float, default=None,
                   help="per-request wall-clock deadline in seconds, "
                        "enforced at decode-tick boundaries: overdue "
                        "pending requests are rejected unserved, overdue "
                        "active ones retire with the tokens they have")
    p.add_argument("--shed-threshold", type=int, default=None,
                   help="admission backlog cap: while active+pending "
                        "exceeds it the newest arrivals are shed and "
                        "/healthz answers 503 until the backlog drains")
    p.add_argument("--measure", choices=["wallclock", "sim"], default=None,
                   help="re-measure model top-k candidates on the serving "
                        "path: 'wallclock' times the compiled kernels and "
                        "needs a TPU, 'sim' uses the analytic backend")
    args = p.parse_args()

    cfg = smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if jax.default_backend() != "tpu" and not args.smoke:
        # a published config serves on the chip: without one, JAX would
        # quietly run it on the CPU (the kernels interpreted)
        raise SystemExit(f"{cfg.name} serves on a TPU and JAX found "
                         f"{jax.default_backend()!r}; use --smoke here")
    if cfg.is_encdec:
        raise SystemExit("enc-dec serving is exercised via the dry-run "
                         "decode cells; the engine serves LM archs")

    enable_compile_cache()
    params = init_params(cfg, jax.random.PRNGKey(0))
    eng = Engine(cfg, params, ServeConfig(
        max_len=args.max_len, slots=args.slots,
        temperature=args.temperature, tunedb=args.tunedb,
        tunedb_backend=args.tunedb_backend, admission=args.admission,
        retune=args.retune,
        retune_interval=args.retune_interval,
        retune_async=args.retune_async,
        retune_fleet=args.retune_fleet,
        retune_cooldown_ticks=args.retune_cooldown_ticks,
        retune_max_sessions=args.retune_max_sessions,
        retune_window_s=args.retune_window,
        retune_min_gain=args.retune_min_gain,
        retune_sentry=args.retune_sentry,
        plan_dir=args.plan_dir,
        follow=args.follow,
        follow_interval_s=args.follow_interval,
        retune_publish=args.retune_publish,
        telemetry_export_s=args.telemetry_export,
        router=args.router,
        status_port=args.status_port,
        trace_sample=args.trace_sample,
        request_deadline_s=args.request_deadline,
        shed_threshold=args.shed_threshold,
        measure=args.measure))
    if eng.status_server is not None:
        print(f"status endpoint: {eng.status_server.url} "
              f"(/metrics /status /plan /trace /healthz)")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, args.prompt_len)
               for _ in range(args.requests)]
    import time
    t0 = time.perf_counter()
    outs = eng.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"{len(outs)} requests, {total} tokens in {dt:.2f}s "
          f"({total/dt:.1f} tok/s, {eng.ticks} decode ticks, "
          f"{total/max(eng.ticks,1):.2f} tokens/tick)")
    if args.shed_threshold is not None or args.request_deadline is not None:
        print(f"degradation: {eng.shed_requests} request(s) shed, "
              f"{eng.deadline_retired} deadline-retired")
    if eng.controller is not None:
        if eng.controller.async_active():
            print("waiting for the in-flight async retune to land...")
            if (eng.controller.wait_async(timeout=60.0) is None
                    and eng.controller.async_active()):
                # a fleet with no live workers can outwait this launcher;
                # the published jobs persist on the bus either way
                print("async retune still in flight after 60s — exiting; "
                      "fleet jobs stay queued (run `fleet worker` / "
                      "`fleet drain --wait` to finish and merge them)")
        st = eng.controller.stats()
        print(f"retune: {st['retunes']} epoch(s) over {st['checks']} polls, "
              f"serving generation {st['generation']} "
              f"(telemetry scope: {st['telemetry_scope']})")
    if eng.router is not None:
        rt = eng.router.stats()
        print(f"router[{rt['policy']}]: {rt['decisions']} decision(s) "
              f"by outcome {rt['outcomes']}")
    if eng.tracer is not None:
        ts = eng.tracer.stats()
        print(f"trace: {ts['sampled']} root(s) sampled, "
              f"{ts['dropped']} dropped, {ts['spans']} span(s) retained")
        if args.trace_out:
            n = eng.tracer.export(args.trace_out)
            print(f"trace: wrote {n} span(s) -> {args.trace_out} "
                  "(open in https://ui.perfetto.dev)")


if __name__ == "__main__":
    main()
