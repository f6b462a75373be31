"""Production training driver: composes config, mesh, sharded step, data
pipeline, checkpointing, fault tolerance and (optional) elastic restart.

Runs anywhere a mesh fits — the production 16x16/2x16x16 pods on real
hardware, or a debug mesh on CPU (used by `examples/train_lm.py` and the
integration tests with reduced configs).

    python -m repro.launch.train --arch qwen2-1.5b --steps 200 \
        --ckpt-dir /tmp/ckpt [--reduced] [--mesh 2x2]
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Optional

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.data.tokens import SyntheticTokenPipeline, TokenPipelineConfig
from repro.launch import sharding as shard_lib
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.steps import (AdamWConfig, TrainState, make_train_step)
from repro.models import init_model
from repro.optim import init_adamw
from repro.runtime import PreemptionHandler, StepWatchdog
from repro.runtime.elastic import reshard_state, shardings_for


@dataclasses.dataclass
class TrainLoopConfig:
    arch: str
    steps: int = 100
    seq_len: int = 128
    global_batch: int = 8
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 50
    log_every: int = 10
    reduced: bool = True
    mesh_shape: Optional[tuple] = None   # e.g. (2, 2); None = production
    lr: float = 3e-4
    warmup_steps: int = 20
    seed: int = 0


def build_mesh(loop_cfg: TrainLoopConfig):
    if loop_cfg.mesh_shape is None:
        return make_production_mesh()
    return make_mesh(loop_cfg.mesh_shape, ("data", "model"))


def train(loop_cfg: TrainLoopConfig, emit=print) -> dict:
    cfg = get_config(loop_cfg.arch)
    if loop_cfg.reduced:
        cfg = reduced_config(cfg)
        cfg = dataclasses.replace(
            cfg, tp_size=(loop_cfg.mesh_shape or (1, 1))[1])
    mesh = build_mesh(loop_cfg)
    shape = ShapeConfig("loop", loop_cfg.seq_len, loop_cfg.global_batch,
                        "train")

    pipeline = SyntheticTokenPipeline(TokenPipelineConfig(
        vocab_size=cfg.vocab_size, seq_len=loop_cfg.seq_len,
        global_batch=loop_cfg.global_batch, seed=loop_cfg.seed))

    mgr = (CheckpointManager(loop_cfg.ckpt_dir)
           if loop_cfg.ckpt_dir else None)
    watchdog = StepWatchdog()
    preempt = PreemptionHandler().install()

    with mesh:
        plan = make_train_step(cfg, mesh, shape,
                               opt_cfg=AdamWConfig(lr=loop_cfg.lr),
                               total_steps=loop_cfg.steps,
                               warmup_steps=loop_cfg.warmup_steps,
                               sequence_parallel=False)
        params, specs = init_model(cfg, jax.random.PRNGKey(loop_cfg.seed))
        state = TrainState(params=params, opt=init_adamw(params))
        # Place per the plan's shardings (debug meshes included).
        state = reshard_state(
            state, mesh,
            TrainState(params=shard_lib.adapt_specs_for_mesh(specs, mesh),
                       opt=plan_opt_specs(cfg, mesh, specs, params)))

        start_step = 0
        if mgr is not None and mgr.latest_step() is not None:
            state = mgr.restore(state)
            start_step = mgr.latest_step()
            emit(f"[train] resumed from step {start_step}")

        losses = []
        t_last = time.perf_counter()
        step = start_step
        for step in range(start_step, loop_cfg.steps):
            batch = {k: jax.numpy.asarray(v)
                     for k, v in pipeline.batch_at(step).items()}
            if cfg.encoder_layers:
                batch["enc_emb"] = jax.numpy.zeros(
                    (loop_cfg.global_batch, cfg.encoder_seq_len,
                     cfg.d_model), jax.numpy.float32)
            state, metrics = plan.step_fn(state, batch)
            loss = float(metrics["loss"])
            losses.append(loss)
            now = time.perf_counter()
            report = watchdog.observe(step, now - t_last)
            if report is not None:
                emit(f"[train] straggler step {step}: "
                     f"{report.duration:.3f}s ({report.ratio:.1f}x EMA)")
            t_last = now
            if step % loop_cfg.log_every == 0:
                emit(f"[train] step {step} loss {loss:.4f} "
                     f"gnorm {float(metrics['grad_norm']):.3f}")
            if mgr is not None and (step + 1) % loop_cfg.ckpt_every == 0:
                mgr.save(step + 1, state, blocking=False)
            if preempt.preemption_requested:
                emit(f"[train] preemption at step {step}; checkpointing")
                if mgr is not None:
                    mgr.save(step + 1, state, blocking=True)
                break
        if mgr is not None:
            mgr.save(step + 1, state, blocking=True)
            mgr.wait()
    preempt.uninstall()
    return {"final_loss": losses[-1] if losses else float("nan"),
            "losses": losses, "last_step": step + 1,
            "straggler_reports": len(watchdog.reports)}


def plan_opt_specs(cfg, mesh, param_specs, params):
    from repro.optim import zero_specs
    shapes = jax.tree_util.tree_map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype), params)
    return zero_specs(shard_lib.adapt_specs_for_mesh(param_specs, mesh),
                      dict(mesh.shape), shapes)


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--ckpt-dir", type=str, default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--reduced", action="store_true", default=True)
    p.add_argument("--full", dest="reduced", action="store_false")
    p.add_argument("--mesh", type=str, default=None,
                   help="e.g. '2x2' for a debug mesh; default production")
    p.add_argument("--lr", type=float, default=3e-4)
    args = p.parse_args(argv)
    mesh_shape = (tuple(int(x) for x in args.mesh.split("x"))
                  if args.mesh else None)
    out = train(TrainLoopConfig(
        arch=args.arch, steps=args.steps, seq_len=args.seq_len,
        global_batch=args.global_batch, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every, reduced=args.reduced,
        mesh_shape=mesh_shape, lr=args.lr))
    print(f"[train] done: {out['last_step']} steps, "
          f"final loss {out['final_loss']:.4f}")


if __name__ == "__main__":
    main()
