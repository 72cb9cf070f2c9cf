"""Production training launcher.

Runs the jitted train step on the active mesh with checkpoint/restart,
deterministic data sharding, and straggler/failure handling hooks.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b \
        --steps 200 --design design2 --backend residual_xla \
        --ckpt-dir /tmp/ck [--smoke] [--mesh host|single|multi]

Fault-tolerance contract (see DESIGN.md §4):
  * restart-safe: restores params/opt/step from the newest intact
    checkpoint (corrupt ones are skipped via manifest hashes);
  * elastic: restore re-shards onto whatever mesh is active;
  * data: batch(step) is stateless -> no loader state to recover;
  * stragglers: per-step wall-time EWMA is logged; steps exceeding
    `--straggler-factor` x EWMA emit a warning (on real fleets this
    triggers hot-spare swap; here it is observability).
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import DataConfig, host_batch
from repro.models import transformer as T
from repro.models.sharding import SINGLE_POD_RULES, logical_axis_rules
from repro.quant import QuantConfig
from repro.train import OptConfig, checkpoint as ckpt, make_train_step
from repro.train import optimizer as opt_mod
from .mesh import make_host_mesh, make_production_mesh


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--design", default="design2")
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--quant-mode", default="asym_u8",
                    choices=["asym_u8", "sym_i8"])
    ap.add_argument("--plan", default=None, metavar="FILE",
                    help="DesignPlan JSON (repro.calib.plan): QAT "
                         "through the planned per-layer designs — raw "
                         "params are wrapped with the plan's delta "
                         "tables inside the loss, so the optimizer and "
                         "checkpoints stay on plain float weights")
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", choices=["host", "single", "multi"],
                    default="host")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--straggler-factor", type=float, default=3.0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    qcfg = QuantConfig(design=args.design, backend=args.backend,
                       mode=args.quant_mode)
    ocfg = OptConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 5),
                     total_steps=args.steps,
                     compress_grads=args.compress_grads)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch)

    mesh = {"host": make_host_mesh,
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))

    with mesh, logical_axis_rules(SINGLE_POD_RULES, sizes):
        params = T.init_params(jax.random.PRNGKey(0), cfg)
        params_transform = None
        if args.plan:
            from repro.calib import DesignPlan, make_plan_injector
            plan = DesignPlan.load(args.plan)
            params_transform = make_plan_injector(params, plan, qcfg)
            print(f"[train] QAT through design plan {args.plan} "
                  f"(histogram {plan.histogram()})")
        opt_state = opt_mod.init(params, ocfg)
        start = 0
        if args.ckpt_dir and ckpt.latest_step(args.ckpt_dir) is not None:
            tmpl = {"params": params, "opt": opt_state}
            restored, start = ckpt.restore(args.ckpt_dir, tmpl)
            params, opt_state = restored["params"], restored["opt"]
            print(f"[train] restored checkpoint at step {start}")

        step_fn = jax.jit(make_train_step(cfg, qcfg, ocfg,
                                          microbatches=args.microbatches,
                                          remat=not args.smoke,
                                          params_transform=params_transform),
                          donate_argnums=(0, 1))
        ewma = None
        for step in range(start, args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in host_batch(dcfg, step).items()}
            t0 = time.perf_counter()
            params, opt_state, metrics = step_fn(params, opt_state, batch)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            ewma = dt if ewma is None else 0.9 * ewma + 0.1 * dt
            if dt > args.straggler_factor * ewma and step > start + 3:
                print(f"[train][straggler] step {step} took {dt:.2f}s "
                      f"(ewma {ewma:.2f}s) — flagging for mitigation")
            if step % args.log_every == 0:
                print(f"[train] step {step:5d} loss {loss:8.4f} "
                      f"({dt*1e3:.0f} ms)")
            if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt_dir, step + 1,
                          {"params": params, "opt": opt_state})
        if args.ckpt_dir:
            ckpt.save(args.ckpt_dir, args.steps,
                      {"params": params, "opt": opt_state})
        print(f"[train] done at step {args.steps}, final loss {loss:.4f}")
        return loss


if __name__ == "__main__":
    # the CLI entry point owns the persistent compile cache
    from repro.kernels import platform
    platform.enable_compile_cache()
    main()
