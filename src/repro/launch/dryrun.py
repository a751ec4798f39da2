import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^^ MUST precede any jax import: jax locks the device count on first init.
# This module is the ONLY place the 512 placeholder devices exist; tests and
# benchmarks see the real single CPU device.  The dry-run compiles for the
# virtual CPU mesh, so on a TPU host it must not open (and hold) the chip.

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell the production step function is lowered against
ShapeDtypeStruct stand-ins (no allocation), compiled for the target mesh, and
the compiled artifact is mined for:
  * memory_analysis()  — proves the cell fits v5e HBM (per-device);
  * cost_analysis()    — per-device FLOPs / bytes for the roofline terms;
  * HLO collective ops — per-device collective bytes (analysis/hlo.py).

Artifacts land in results/dryrun/<arch>--<shape>--<mesh>.json; the roofline
table and EXPERIMENTS.md sections are generated from them (benchmarks and
analysis never re-compile).

Usage:
  python -m repro.launch.dryrun --all                  # every cell, resumable
  python -m repro.launch.dryrun --arch qwen3-14b --shape train_4k --mesh pod
  python -m repro.launch.dryrun ... --override remat=False --tag exp1
"""

import argparse
import dataclasses
import json
import pathlib
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.hlo import collective_bytes
from repro.analysis.roofline import model_flops
from repro.configs import ARCH_NAMES, SHAPES, applicable, get_config
from repro.configs.shapes import batch_specs, cache_specs, decode_specs
from repro.launch.mesh import make_production_mesh
from repro.models import ModelConfig, decode_step, loss_fn, prefill
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.parallel import sharding as shd

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"


def opt_config_for(cfg: ModelConfig) -> AdamWConfig:
    """bf16 optimizer states above ~20B params (DESIGN.md §7 memory math)."""
    big = cfg.param_count > 2e10
    return AdamWConfig(state_dtype=jnp.bfloat16 if big else jnp.float32)


# ---------------------------------------------------------------------------
# step builders (one per shape kind)
# ---------------------------------------------------------------------------

def train_microbatches(cfg: ModelConfig) -> int:
    """Gradient-accumulation factor for the dry-run training step: bounds
    live activation memory for the huge configs (DESIGN.md §7)."""
    if cfg.param_count > 2e11:
        return 8
    if cfg.param_count > 5e10:
        return 4
    return 1


def grad_accum_dtype(cfg: ModelConfig):
    """f32 gradient accumulators except at 405B scale, where the extra
    params-sized f32 buffer alone would blow the single-pod HBM budget;
    bf16 accumulation over <=8 microbatches is the documented trade."""
    return jnp.bfloat16 if cfg.param_count > 2e11 else jnp.float32


def build_train(cfg: ModelConfig, mesh, shape):
    opt_cfg = opt_config_for(cfg)
    nm = train_microbatches(cfg)
    acc_dt = grad_accum_dtype(cfg)

    def init_state():
        from repro.models import init_params
        params = init_params(cfg, jax.random.PRNGKey(0))
        return {"params": params, "opt": adamw_init(params, opt_cfg)}

    def step(state, batch):
        params = state["params"]
        with shd.use_rules(mesh):
            if nm > 1:
                # microbatch dim is provided by the host batch layout
                # (mb, B/mb, ...), so no resharding reshape is needed
                def micro(gsum, mb):
                    (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(
                        params, cfg, mb)
                    gsum = jax.tree_util.tree_map(
                        lambda a, b: a + b.astype(acc_dt), gsum, g)
                    return gsum, (l, aux["acc"])
                g0 = jax.tree_util.tree_map(
                    lambda p: jnp.zeros(p.shape, acc_dt), params)
                gsum, (ls, accs) = jax.lax.scan(
                    micro, g0, batch, unroll=bool(cfg.unroll_scan))
                grads = jax.tree_util.tree_map(lambda g: g / nm, gsum)
                loss, acc = ls.mean(), accs.mean()
            else:
                (loss, aux), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(params, cfg, batch)
                acc = aux["acc"]
            params, opt, om = adamw_update(params, grads, state["opt"],
                                           opt_cfg)
        return ({"params": params, "opt": opt},
                {"loss": loss, "acc": acc, **om})

    state_t = jax.eval_shape(init_state)
    state_sh = shd.param_shardings(state_t, mesh)
    state_specs = jax.tree_util.tree_map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        state_t, state_sh)
    b_specs = batch_specs(cfg, shape.name, mesh)
    if nm > 1:
        def micro_spec(s):
            B = s.shape[0]
            assert B % nm == 0, (B, nm)
            sh = None
            if s.sharding is not None:
                spec = s.sharding.spec
                sh = jax.sharding.NamedSharding(
                    mesh, jax.sharding.PartitionSpec(None, *spec))
            return jax.ShapeDtypeStruct((nm, B // nm) + s.shape[1:],
                                        s.dtype, sharding=sh)
        b_specs = jax.tree_util.tree_map(micro_spec, b_specs)
    jitted = jax.jit(step, donate_argnums=(0,),
                     out_shardings=(state_sh, None))
    return jitted, (state_specs, b_specs)


def build_prefill(cfg: ModelConfig, mesh, shape):
    def step(params, batch, cache):
        with shd.use_rules(mesh):
            return prefill(params, cfg, batch, cache)

    from repro.models import init_params
    params_t = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params_sh = shd.param_shardings(params_t, mesh)
    params_specs = jax.tree_util.tree_map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        params_t, params_sh)
    b_specs = batch_specs(cfg, shape.name, mesh)
    c_specs = cache_specs(cfg, shape.global_batch, shape.seq_len, mesh)
    cache_sh = jax.tree_util.tree_map(lambda s: s.sharding, c_specs)
    jitted = jax.jit(step, donate_argnums=(2,),
                     out_shardings=(None, cache_sh))
    return jitted, (params_specs, b_specs, c_specs)


def build_decode(cfg: ModelConfig, mesh, shape):
    def step(params, tokens, cache, index, memory=None):
        with shd.use_rules(mesh):
            return decode_step(params, cfg, tokens, cache, index,
                               memory=memory)

    from repro.models import init_params
    params_t = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
    params_sh = shd.param_shardings(params_t, mesh)
    params_specs = jax.tree_util.tree_map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        params_t, params_sh)
    d = decode_specs(cfg, shape.name, mesh)
    cache_sh = jax.tree_util.tree_map(lambda s: s.sharding, d["cache"])
    jitted = jax.jit(step, donate_argnums=(2,),
                     out_shardings=(None, cache_sh))
    args = (params_specs, d["tokens"], d["cache"], d["index"])
    if cfg.is_encdec:
        args = args + (d["memory"],)
    return jitted, args


# ---------------------------------------------------------------------------
# one cell
# ---------------------------------------------------------------------------

def run_cell(arch: str, shape_name: str, mesh_name: str, *,
             overrides: Optional[Dict[str, Any]] = None,
             tag: str = "", rules: str = "default",
             verbose: bool = True) -> Dict[str, Any]:
    shd.set_param_rules(rules)
    cfg = get_config(arch)
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multipod"))
    chips = int(np.prod(list(mesh.shape.values())))

    ok, reason = applicable(cfg, shape_name)
    art: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "kind": shape.kind, "tag": tag,
        "params": cfg.param_count, "active_params": cfg.active_param_count,
        "model_flops": model_flops(cfg, shape, kind=shape.kind),
        "overrides": {k: str(v) for k, v in (overrides or {}).items()},
    }
    if not ok:
        art["skipped"] = reason
        return art

    builder = {"train": build_train, "prefill": build_prefill,
               "decode": build_decode}[shape.kind]
    bits = 16 if cfg.dtype == jnp.bfloat16 else 32

    # ---- pass 1: full-depth scanned compile -> memory analysis -------------
    # (XLA cost_analysis counts a while body ONCE, so flops/bytes/collectives
    #  come from the unrolled reduced-depth passes below instead.)
    t0 = time.time()
    jitted, specs = builder(cfg, mesh, shape)
    compiled = jitted.lower(*specs).compile()
    t_compile = time.time() - t0
    ma = compiled.memory_analysis()
    ca_raw = compiled.cost_analysis() or {}

    # ---- pass 2+3: unrolled depth-R compiles -> exact linear cost model ----
    def cost_at(r: int) -> Dict[str, float]:
        rcfg = dataclasses.replace(
            cfg, n_layers=len(cfg.pattern) * r,
            encoder_layers=(r if cfg.is_encdec else 0),
            unroll_scan=True)
        j, sp = builder(rcfg, mesh, shape)
        comp = j.lower(*sp).compile()
        c = comp.cost_analysis() or {}
        coll = collective_bytes(comp.as_text(), normalize_bits=bits)
        return {"flops": float(c.get("flops", 0.0)),
                "bytes": float(c.get("bytes accessed", 0.0)),
                "coll": {k: float(v) for k, v in coll.items()}}

    t0 = time.time()
    c1, c2 = cost_at(1), cost_at(2)
    t_cost = time.time() - t0
    R = cfg.n_repeats

    def extrap(a1: float, a2: float) -> float:
        return a1 + (R - 1) * (a2 - a1)

    flops = extrap(c1["flops"], c2["flops"])
    bytes_acc = extrap(c1["bytes"], c2["bytes"])
    colls = {k: extrap(c1["coll"][k], c2["coll"][k]) for k in c1["coll"]}

    arg = int(getattr(ma, "argument_size_in_bytes", 0))
    out_b = int(getattr(ma, "output_size_in_bytes", 0))
    tmp = int(getattr(ma, "temp_size_in_bytes", 0))
    alias = int(getattr(ma, "alias_size_in_bytes", 0))
    art.update({
        "cost": {"flops": flops, "bytes_accessed": bytes_acc,
                 "flops_depth1": c1["flops"], "flops_depth2": c2["flops"],
                 "flops_scanned_raw": float(ca_raw.get("flops", 0.0))},
        "memory": {"argument": arg, "output": out_b, "temp": tmp,
                   "alias": alias,
                   "peak_per_device": arg + out_b + tmp - alias,
                   # XLA:CPU upcasts bf16 compute to f32, inflating temp
                   # buffers ~2x vs the TPU lowering; argument/output keep
                   # their declared dtypes.  The estimate halves temp for
                   # bf16 models (fp32 accumulators make it conservative
                   # only to first order — recorded as an ESTIMATE).
                   "peak_per_device_bf16_est":
                       arg + out_b - alias + (tmp // 2 if bits == 16
                                              else tmp)},
        "collectives": colls,
        "compile_s": round(t_compile, 2), "cost_pass_s": round(t_cost, 2),
    })
    if verbose:
        print(f"[{arch} | {shape_name} | {mesh_name}] "
              f"compile {t_compile:.1f}s (+{t_cost:.1f}s cost passes)  "
              f"flops/dev {flops:.3e}  "
              f"peak/dev {art['memory']['peak_per_device']/2**30:.2f} GiB  "
              f"coll/dev {colls['total']/2**20:.1f} MiB")
        print(f"  memory_analysis: {ma}")
    return art


def artifact_path(arch: str, shape_name: str, mesh_name: str,
                  tag: str = "") -> pathlib.Path:
    t = f"--{tag}" if tag else ""
    return RESULTS / f"{arch}--{shape_name}--{mesh_name}{t}.json"


def main() -> int:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", choices=ARCH_NAMES)
    p.add_argument("--shape", choices=list(SHAPES))
    p.add_argument("--mesh", choices=["pod", "multipod"], default="pod")
    p.add_argument("--all", action="store_true")
    p.add_argument("--force", action="store_true")
    p.add_argument("--tag", default="")
    p.add_argument("--rules", default="default",
                   choices=["default", "dp_only"],
                   help="parameter-sharding rule set (perf experiments)")
    p.add_argument("--override", action="append", default=[],
                   help="ModelConfig field override, e.g. remat=False")
    args = p.parse_args()

    overrides: Dict[str, Any] = {}
    for ov in args.override:
        k, v = ov.split("=", 1)
        overrides[k] = (False if v == "False" else True if v == "True"
                        else int(v) if v.lstrip("-").isdigit() else
                        float(v) if "." in v else v)

    cells = []
    if args.all:
        for a in ARCH_NAMES:
            for s in SHAPES:
                for m in ("pod", "multipod"):
                    cells.append((a, s, m))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape, args.mesh)]

    RESULTS.mkdir(parents=True, exist_ok=True)
    failures = 0
    for arch, s, m in cells:
        path = artifact_path(arch, s, m, args.tag)
        if path.exists() and not args.force:
            print(f"[skip existing] {path.name}")
            continue
        try:
            art = run_cell(arch, s, m, overrides=overrides or None,
                           tag=args.tag, rules=args.rules)
            art["rules"] = args.rules
            path.write_text(json.dumps(art, indent=1))
        except Exception:
            failures += 1
            print(f"[FAIL] {arch} {s} {m}")
            traceback.print_exc()
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
