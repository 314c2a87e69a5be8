"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
        --steps 200 --ckpt-dir /tmp/ckpt

Features exercised here (pod-scale mechanics on any backend):
  * sharded params/optimizer via the logical-axis resolver
  * donated buffers (in-place param/opt updates)
  * microbatch gradient accumulation, optional gradient compression
  * async checkpointing + retention + resume (picks up after kill -9)
  * preemption handler (SIGTERM -> final checkpoint -> clean exit)
  * straggler monitor + prefetching data pipeline
"""
from __future__ import annotations

import argparse
import functools
import json
import sys
import time

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.checkpoint.checkpointer import Checkpointer
from repro.compile_cache import place_compile_cache
from repro.configs import get_arch
from repro.data.pipeline import Prefetcher, SyntheticLMData
from repro.distributed.compression import GradientCompressor
from repro.distributed.fault import PreemptionHandler, StragglerMonitor
from repro.distributed.sharding import default_rules, shapes_shardings_from_axes
from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.models.lm import LM
from repro.nn.types import split
from repro.train.optimizer import Optimizer, OptimizerConfig, cosine_schedule
from repro.train.step import make_train_step


def parse_args(argv=None) -> argparse.Namespace:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-1.7b")
    p.add_argument("--smoke", action="store_true", help="reduced config (CPU-sized)")
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--global-batch", type=int, default=8)
    p.add_argument("--microbatches", type=int, default=1)
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--mesh", default="host", choices=["host", "single", "multi"])
    p.add_argument("--ckpt-dir", default=None)
    p.add_argument("--ckpt-every", type=int, default=50)
    p.add_argument("--compression", action="store_true")
    p.add_argument("--log-every", type=int, default=10)
    return p.parse_args(argv)


def run(args: argparse.Namespace) -> dict:
    """Train per ``args``; returns the final-step summary."""
    arch = get_arch(args.arch)
    spec = arch.smoke_spec_fn() if args.smoke else arch.spec()
    model = LM(spec)
    mesh = {"host": make_host_mesh,
            "single": lambda: make_production_mesh(multi_pod=False),
            "multi": lambda: make_production_mesh(multi_pod=True)}[args.mesh]()
    rules = default_rules(mesh)
    rep = NamedSharding(mesh, PartitionSpec())

    annotated = model.init(jax.random.PRNGKey(0), dtype=jnp.float32)
    params, axes = split(annotated)
    param_sh = shapes_shardings_from_axes(params, axes, mesh, rules)
    params = jax.device_put(params, param_sh)

    optimizer = Optimizer(OptimizerConfig(
        name="adamw",
        learning_rate=cosine_schedule(args.lr, warmup=max(1, args.steps // 20), total=args.steps),
    ))
    opt_state = jax.device_put(optimizer.init(params), {"step": rep, "mu": param_sh, "nu": param_sh})

    compressor = GradientCompressor() if args.compression else None
    compress_state = compressor.init_state(params) if compressor else None
    step_fn = make_train_step(model, optimizer, microbatches=args.microbatches,
                              compressor=compressor)
    donate = (0, 1)
    jit_step = jax.jit(step_fn, donate_argnums=donate)

    data = SyntheticLMData(spec.vocab, args.seq, args.global_batch)
    ckpt = Checkpointer(args.ckpt_dir, keep=3) if args.ckpt_dir else None
    start_step = 0
    if ckpt is not None and ckpt.latest_step() is not None:
        state_like = {"params": params, "opt": opt_state}
        state_sh = {"params": param_sh, "opt": {"step": rep, "mu": param_sh, "nu": param_sh}}
        start_step, restored = ckpt.restore(like=state_like, shardings=state_sh)
        params, opt_state = restored["params"], restored["opt"]
        print(f"[train] resumed from step {start_step}", flush=True)

    prefetch = Prefetcher(data, start_step=start_step)
    preempt = PreemptionHandler()
    straggler = StragglerMonitor()
    metrics = {}
    with mesh:
        for _ in range(start_step, args.steps):
            t0 = time.time()
            step_idx, batch = prefetch.next()
            if compressor:
                params, opt_state, metrics, compress_state = jit_step(
                    params, opt_state, batch, compress_state)
            else:
                params, opt_state, metrics = jit_step(params, opt_state, batch)
            dt = time.time() - t0
            slow = straggler.record(dt)
            if (step_idx + 1) % args.log_every == 0:
                loss = float(metrics["loss"])
                print(f"[train] step {step_idx + 1} loss {loss:.4f} "
                      f"({dt * 1e3:.0f} ms{' STRAGGLER' if slow else ''})", flush=True)
            if ckpt is not None and (step_idx + 1) % args.ckpt_every == 0:
                ckpt.save_async(step_idx + 1, {"params": params, "opt": opt_state})
            if preempt.preempted:
                print("[train] preemption: flushing checkpoint", flush=True)
                if ckpt is not None:
                    ckpt.save(step_idx + 1, {"params": params, "opt": opt_state})
                break
    if ckpt is not None:
        ckpt.wait()
    prefetch.close()
    return {"final_loss": float(metrics.get("loss", float("nan"))),
            "straggler_flags": straggler.flags,
            "platform": mesh.devices.flat[0].platform}


def main(argv=None) -> int:
    args = parse_args(argv)
    place_compile_cache()
    print(json.dumps(run(args)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
