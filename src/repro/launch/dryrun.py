import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# The two lines above MUST run before any jax-importing module: jax locks the
# device count at first init, and the production meshes below need 512
# placeholder host devices (16x16 single-pod, 2x16x16 multi-pod).
import argparse     # noqa: E402
import json         # noqa: E402
import time         # noqa: E402
import traceback    # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp                      # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.configs import (ARCHS, SHAPES, input_specs, reduce_config,  # noqa: E402
                           skip_reason)
from repro.launch.hlo_stats import collective_bytes          # noqa: E402
from repro.launch.mesh import (batch_sharding, batch_spec,   # noqa: E402
                               make_production_mesh, rules_for)
from repro.models import build_model                         # noqa: E402
from repro.models import transformer as tfm                  # noqa: E402
from repro.launch.hlo_cost import analyze_compiled           # noqa: E402
from repro.train import TrainStepConfig, make_train_step     # noqa: E402
from repro.train.optimizer import adamw_init, opt_state_specs  # noqa: E402


def _fix_batch_dim(spec_tree, rules, B):
    """Replace data-axis entries in cache specs with the batch-size-aware
    sharding (long_500k has global_batch=1, which cannot shard 16 ways)."""
    bs = batch_spec(rules, B)
    repl = bs[0] if len(bs) else None
    data_entries = {rules.data, tuple(rules.data_axes), *rules.data_axes}

    def fix(p):
        parts = []
        for e in p:
            key = tuple(e) if isinstance(e, (tuple, list)) else e
            parts.append(repl if key in data_entries else e)
        return P(*parts)

    return jax.tree.map(fix, spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


def _spec_step(cfg, shape, rules, microbatches: int,
               accumulation: str = "grad"):
    """Build (fn, arg_shapes, in_shardings, out_shardings) for one cell."""
    model = build_model(cfg)
    key = jax.random.PRNGKey(0)
    params_shape = jax.eval_shape(model.init, key)
    pspecs = model.param_specs(rules)
    batch = input_specs(cfg, shape)
    bspecs = batch_sharding(rules, batch)

    if shape.kind == "train":
        opt_shape = jax.eval_shape(adamw_init, params_shape)
        ospecs = opt_state_specs(pspecs)
        step = make_train_step(
            model.loss_fn,
            TrainStepConfig(microbatches=microbatches,
                            accumulation=accumulation),
            rules=rules)
        args = (params_shape, opt_shape, batch,
                jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = (pspecs, ospecs, bspecs, None)
        out_sh = (pspecs, ospecs, None)
        return step, args, in_sh, out_sh, (0, 1)   # donate params + opt

    if shape.kind == "prefill":
        if cfg.family == "encoder":
            def enc(params, batch):
                return tfm.encode_step(params, batch, cfg, rules=rules)
            return enc, (params_shape, batch), (pspecs, bspecs), None, ()
        B = shape.global_batch
        cache_shape = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len))
        cspecs = _fix_batch_dim(model.cache_specs(rules), rules, B)

        def pf(params, batch, cache):
            return model.prefill(params, batch, cache, rules=rules)
        return (pf, (params_shape, batch, cache_shape),
                (pspecs, bspecs, cspecs), (None, cspecs), (2,))  # donate cache

    # decode
    B = shape.global_batch
    cache_shape = jax.eval_shape(lambda: model.init_cache(B, shape.seq_len))
    cspecs = _fix_batch_dim(model.cache_specs(rules), rules, B)
    tok = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    tspec = P(*(tuple(batch_spec(rules, B)) + (None,)))
    pos = jax.ShapeDtypeStruct((), jnp.int32)

    def dec(params, cache, tokens, pos):
        return model.decode_step(params, cache, tokens, pos, rules=rules)
    return (dec, (params_shape, cache_shape, tok, pos),
            (pspecs, cspecs, tspec, None), (None, cspecs), (1,))  # donate cache


def _analyze(fn, args, in_sh, out_sh, save_hlo=None, donate=()):
    """jit + lower + compile + trip-count-aware HLO cost extraction.

    ``donate``: argnums whose buffers the step owns (params/opt for train,
    the KV cache for serve) — production steps always donate these, and
    without it XLA materializes a full copy of every functionally-updated
    state tensor (the decode cache copy alone is ~90x the attention reads).
    """
    t0 = time.time()
    jitted = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                     donate_argnums=donate)
    lowered = jitted.lower(*args)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    xla_cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    if save_hlo:
        with open(save_hlo, "w") as f:
            f.write(hlo)
    rec = {
        "lower_s": round(t_lower, 2),
        "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "code_bytes": mem.generated_code_size_in_bytes,
        },
        # trip-count-aware per-device costs (launch/hlo_cost.py); XLA's own
        # cost_analysis counts while-loop bodies once, so it is recorded only
        # for reference as "xla_*"
        "cost": analyze_compiled(compiled),
        "xla_flops_body_once": float(xla_cost.get("flops", 0.0)),
        "xla_bytes_body_once": float(xla_cost.get("bytes accessed", 0.0)),
        "collectives_body_once": collective_bytes(hlo),
    }
    rec["flops_per_device"] = rec["cost"]["flops"]
    rec["bytes_per_device"] = rec["cost"]["bytes"]
    rec["collectives"] = rec["cost"]["collectives"]
    return rec


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 8, save_hlo: str | None = None,
             arch_override=None, accumulation: str = "grad",
             data_only: bool = False) -> dict:
    """Lower + compile one (arch x shape x mesh) cell; return its record."""
    cfg = arch_override if arch_override is not None else ARCHS[arch]
    shape = SHAPES[shape_name]
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": shape.kind}
    reason = skip_reason(arch, shape_name)
    if reason:
        rec["skipped"] = reason
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    rules = rules_for(mesh, data_only=data_only)
    rec["devices"] = mesh.devices.size
    rec["variant"] = {"accumulation": accumulation, "data_only": data_only}

    with jax.set_mesh(mesh):
        fn, args, in_sh, out_sh, donate = _spec_step(cfg, shape, rules,
                                                     microbatches,
                                                     accumulation)
        rec.update(_analyze(fn, args, in_sh, out_sh, save_hlo=save_hlo,
                            donate=donate))
    rec["microbatches"] = microbatches if shape.kind == "train" else 1
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="one arch id (default: all)")
    ap.add_argument("--shape", default=None, choices=list(SHAPES),
                    help="one shape (default: all)")
    ap.add_argument("--mesh", default="both",
                    choices=["pod", "multipod", "both"])
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--smoke-config", action="store_true",
                    help="use the reduced config (debugging the harness)")
    ap.add_argument("--accumulation", default="grad",
                    choices=["grad", "loss"],
                    help="microbatch gradient accumulation mode (Perf)")
    ap.add_argument("--data-only", action="store_true",
                    help="fold the model axis into data parallelism (Perf)")
    ap.add_argument("--suffix", default="",
                    help="output filename suffix for perf variants")
    ap.add_argument("--moe-gather", action="store_true",
                    help="gather-based MoE dispatch/combine (now the "
                         "default; flag kept for provenance)")
    ap.add_argument("--moe-scatter", action="store_true",
                    help="scatter-based MoE dispatch (paper-faithful "
                         "baseline records)")
    ap.add_argument("--tuned", action="store_true",
                    help="apply per-arch tuned launch settings "
                         "(launch/tuned.py; EXPERIMENTS.md §Perf)")
    args = ap.parse_args()

    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = {"pod": [False], "multipod": [True],
              "both": [False, True]}[args.mesh]
    os.makedirs(args.out, exist_ok=True)

    failures = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                mesh_name = "pod2x16x16" if mp else "pod16x16"
                tag = f"{arch}__{shape}__{mesh_name}{args.suffix}"
                hlo_path = (os.path.join(args.out, tag + ".hlo.txt")
                            if args.save_hlo else None)
                override = (reduce_config(ARCHS[arch])
                            if args.smoke_config else None)
                try:
                    from repro.launch.tuned import launch_kwargs
                    from repro.models import moe as moe_mod
                    tk = launch_kwargs(arch, SHAPES[shape].kind, args.tuned)
                    mode = ("scatter" if args.moe_scatter else "gather")
                    with moe_mod.dispatch_mode(mode):
                        rec = run_cell(
                            arch, shape, mp,
                            microbatches=tk.get("microbatches",
                                                args.microbatches),
                            save_hlo=hlo_path,
                            arch_override=override,
                            accumulation=args.accumulation,
                            data_only=tk.get("data_only", args.data_only))
                except Exception as e:  # noqa: BLE001 — record and continue
                    failures += 1
                    rec = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                with open(os.path.join(args.out, tag + ".json"), "w") as f:
                    json.dump(rec, f, indent=2)
                if "skipped" in rec:
                    status = "SKIP " + rec["skipped"]
                elif "error" in rec:
                    status = "FAIL " + rec["error"][:120]
                else:
                    status = (f"ok lower={rec['lower_s']}s "
                              f"compile={rec['compile_s']}s "
                              f"flops/dev={rec['flops_per_device']:.3g} "
                              f"coll={rec['collectives']['total_bytes']:.3g}B")
                print(f"[dryrun] {tag}: {status}", flush=True)
    print(f"[dryrun] done, {failures} failures")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
