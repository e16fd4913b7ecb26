"""End-to-end training driver: data pipeline -> sharded train loop ->
step-atomic checkpoints -> restart/elastic restore.

Run locally (CPU, reduced config):
    PYTHONPATH=src python -m repro.launch.train --arch gemma-2b --smoke \
        --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

Fault-tolerance wiring:
* checkpoints carry params + optimizer + data cursor + RNG seed; a killed
  run resumes bit-identically (tests/test_checkpoint.py);
* fixed-shape batches: a restarted host can never change the collective
  schedule (straggler discipline);
* ``--mesh-shape`` reshards any checkpoint onto the current mesh (elastic
  restart: axis sizes only need to divide the global shapes).
"""
from __future__ import annotations

import argparse
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import ARCHS, reduce_config
from repro.data.tokens import TokenPipeline
from repro.launch.mesh import batch_sharding, rules_for
from repro.models import build_model
from repro.train import TrainStepConfig, make_train_step
from repro.train import checkpoint as ckpt
from repro.train.optimizer import adamw_init, opt_state_specs


def build_mesh(shape, names):
    n = int(np.prod(shape))
    if len(jax.devices()) < n:
        raise SystemExit(
            f"need {n} devices, have {len(jax.devices())}; set "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n}")
    return jax.make_mesh(tuple(shape), tuple(names))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(ARCHS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--mesh-shape", type=int, nargs="+", default=[1, 1])
    ap.add_argument("--mesh-names", nargs="+", default=["data", "model"])
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = ARCHS[args.arch]
    if args.smoke:
        cfg = reduce_config(cfg)
    model = build_model(cfg)

    mesh = build_mesh(args.mesh_shape, args.mesh_names)
    jax.set_mesh(mesh)
    rules = rules_for(mesh)

    pipeline = TokenPipeline(cfg, args.batch, args.seq, seed=args.seed)
    tcfg = TrainStepConfig(peak_lr=args.lr, warmup_steps=min(20, args.steps),
                           total_steps=args.steps,
                           microbatches=args.microbatches)
    step_fn = make_train_step(model.loss_fn, tcfg, rules=rules)

    pspecs = model.param_specs(rules)
    ospecs = opt_state_specs(pspecs)
    psh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs)
    osh = jax.tree.map(lambda s: NamedSharding(mesh, s), ospecs)

    start_step = 0
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(args.seed))
    opt_shape = jax.eval_shape(adamw_init, params_shape)
    latest = ckpt.latest_step(args.ckpt_dir) if args.ckpt_dir else None
    if latest is not None:
        (params, opt_state), extras = ckpt.restore(
            args.ckpt_dir, latest, (params_shape, opt_shape), (psh, osh))
        pipeline.load_state_dict(extras["pipeline"])
        start_step = int(extras["step"]) + 1
        print(f"[train] restored step {latest} "
              f"(cursor={pipeline.cursor})", flush=True)
    else:
        params = jax.jit(model.init, out_shardings=psh)(
            jax.random.PRNGKey(args.seed))
        opt_state = jax.jit(adamw_init, out_shardings=osh)(params)

    batch_sh = None
    jit_step = jax.jit(step_fn, in_shardings=(psh, osh, None, None),
                       out_shardings=(psh, osh, None),
                       donate_argnums=(0, 1))

    t0 = time.time()
    tokens_seen = 0
    for step in range(start_step, args.steps):
        np_batch = next(pipeline)
        if batch_sh is None:
            bspecs = batch_sharding(rules, jax.tree.map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), np_batch))
            batch_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), bspecs)
        batch = jax.tree.map(jax.device_put, np_batch, batch_sh)
        params, opt_state, metrics = jit_step(params, opt_state, batch,
                                              jnp.int32(step))
        tokens_seen += args.batch * args.seq
        if step % args.log_every == 0 or step == args.steps - 1:
            dt = time.time() - t0
            print(f"[train] step {step:5d} loss {float(metrics['loss']):.4f}"
                  f" gnorm {float(metrics['grad_norm']):.3f}"
                  f" lr {float(metrics['lr']):.2e}"
                  f" tok/s {tokens_seen / max(dt, 1e-9):.0f}", flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            path = ckpt.save(args.ckpt_dir, step, (params, opt_state),
                             extras={"step": step,
                                     "pipeline": pipeline.state_dict(),
                                     "arch": cfg.name})
            print(f"[train] checkpoint -> {path}", flush=True)
    print(f"[train] done: {args.steps - start_step} steps in "
          f"{time.time() - t0:.1f}s", flush=True)
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
