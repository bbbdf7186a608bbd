"""Training launcher (CPU-runnable on reduced configs; mesh-agnostic).

    PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
        --steps 30 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Wires together every substrate: data pipeline, sharded step (same builder the
dry-run lowers), checkpoint manager, fault-tolerant loop, straggler monitor,
optional top-k gradient compression.
"""
from __future__ import annotations

import argparse
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.checkpoint import CheckpointManager
from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.data import SyntheticTokens
from repro.launch import sharding as shg
from repro.launch.mesh import make_local_mesh
from repro.models import lm
from repro.optim import adamw_init, adamw_update, cosine_schedule, topk_compress_allreduce
from repro.runtime import FaultTolerantLoop, StepFailure, StragglerMonitor


def build_state(cfg, mesh, tp, seed=0):
    key = jax.random.PRNGKey(seed)
    params = lm.init_params(cfg, key, tp=tp)
    opt = adamw_init(params)
    pspecs = shg.param_specs(cfg, mesh, tp, params)
    pshard = shg.to_shardings(mesh, pspecs)
    oshard = shg.to_shardings(mesh, shg.opt_specs(cfg, mesh, tp, opt, pspecs))
    params = jax.device_put(params, pshard)
    opt = jax.device_put(opt, oshard)
    return params, opt, pshard, oshard


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--grad-compress", type=float, default=0.0,
                    help="top-k compression ratio (0 = exact reduction)")
    ap.add_argument("--inject-failure-at", type=int, default=-1,
                    help="simulate a transient failure at this step (testing)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    compat.enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    mesh = make_local_mesh(args.tp)
    tp = args.tp

    params, opt, pshard, oshard = build_state(cfg, mesh, tp, args.seed)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.2f}M mesh={dict(mesh.shape)}")

    data = SyntheticTokens(cfg, shape, seed=args.seed)
    lr_fn = cosine_schedule(args.lr, max(args.steps // 10, 1), args.steps)
    compress = args.grad_compress

    def step_fn_inner(params, opt, residual, batch):
        (loss, metrics), grads = jax.value_and_grad(
            partial(lm.loss_fn, cfg=cfg, tp=tp), has_aux=True
        )(params, batch=batch)
        if compress > 0:
            grads, residual = topk_compress_allreduce(grads, residual, None, compress)
        params, opt = adamw_update(grads, opt, params, lr_fn(opt["step"]))
        metrics = dict(metrics)
        metrics["loss"] = loss
        return params, opt, residual, metrics

    jitted = jax.jit(step_fn_inner, donate_argnums=(0, 1, 2))
    residual0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

    ckpt = CheckpointManager(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    state = dict(params=params, opt=opt, residual=residual0)
    if ckpt and args.resume and ckpt.latest_step() is not None:
        state, manifest = ckpt.restore(state)
        start_step = manifest["step"]
        print(f"resumed from step {start_step}")

    def step_fn(state, batch):
        p, o, r, metrics = jitted(state["params"], state["opt"], state["residual"], batch)
        return dict(params=p, opt=o, residual=r), {
            k: float(v) for k, v in metrics.items()
        }

    def save_fn(step, state):
        if ckpt:
            ckpt.save(step, state, meta=dict(arch=cfg.name))

    def restore_fn():
        assert ckpt is not None, "restore requires --ckpt-dir"
        st, manifest = ckpt.restore(state)
        return st, manifest["step"]

    def failure_hook(step):
        if step == args.inject_failure_at:
            args.inject_failure_at = -1  # fire once
            raise StepFailure("transient", "injected test failure")

    monitor = StragglerMonitor(hosts=1)
    loop = FaultTolerantLoop(
        step_fn, save_fn, restore_fn, ckpt_every=args.ckpt_every,
        failure_hook=failure_hook,
    )

    def batches(step):
        b = data.batch_at(step)
        return {k: jnp.asarray(v) for k, v in b.items()}

    state, history = loop.run(state, batches, start_step, args.steps)
    for step, m in history[:3] + history[-3:]:
        print(f"step {step:5d} loss={m['loss']:.4f} t={m['step_time_s']*1e3:.0f}ms")
        monitor.observe(np.array([m["step_time_s"]]))
    losses = [m["loss"] for _, m in history]
    print(
        f"done: steps={loop.stats.steps_done} retries={loop.stats.retries} "
        f"restores={loop.stats.restores} loss {losses[0]:.4f} -> {losses[-1]:.4f}"
    )
    return losses


if __name__ == "__main__":
    main()
