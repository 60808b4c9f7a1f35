"""End-to-end training driver: data pipeline -> sharded train loop ->
striped async checkpoints -> elastic recovery.

Runs real steps on whatever devices exist (a reduced config on the CPU
container; the full config on a TPU slice).  The recovery loop follows
DESIGN.md §8: on a (simulated or real) node failure the coordinator plans a
new mesh from survivors, state restores from the last committed manifest,
and the deterministic pipeline replays from the restored step.

Usage (CPU container):
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
      --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.checkpoint.manager import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import TokenPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import build_cell
from repro.models import model as M
from repro.optim.adamw import init_opt_state
from repro.runtime.elastic import ElasticCoordinator
from repro.parallel.sharding import spec_for


def make_train_state(cell, key):
    params = M.init_params(M.param_specs(cell.cfg), key)
    params = jax.device_put(params, cell.in_shardings[0])
    opt = init_opt_state(params)
    opt = jax.device_put(opt, cell.in_shardings[1])
    return params, opt


def train(arch: str, *, steps: int = 20, batch: int = 8, seq: int = 128,
          reduced: bool = True, ckpt_dir: str | None = None,
          ckpt_every: int = 10, resume: bool = True,
          fail_at_step: int | None = None, log_every: int = 1,
          opts: M.RunOptions | None = None, lr_peak: float = 1e-3,
          total_steps: int | None = None):
    cfg = get_config(arch)
    if reduced:
        cfg = reduced_config(cfg)
    shape = ShapeConfig("custom", seq, batch, "train")
    mesh = make_host_mesh()
    opts = opts or M.RunOptions(q_chunk=min(seq, 512), xent_chunk=min(seq, 512))
    cell = build_cell(cfg, shape, mesh, opts=opts, lr_peak=lr_peak,
                      total_steps=total_steps or max(10 * steps, 100))

    step_fn = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                      donate_argnums=cell.donate_argnums)
    pipe = TokenPipeline(cfg.vocab_size, seq, batch, mesh=mesh,
                         batch_spec=spec_for(("batch", None), cell.rules))
    mgr = CheckpointManager(ckpt_dir) if ckpt_dir else None
    coord = ElasticCoordinator(
        hosts=[f"host{i}" for i in range(max(jax.process_count(), 1))],
        devices_per_host=jax.local_device_count(),
        model_parallel=mesh.shape.get("model", 1), num_pods=1)

    start = 0
    with mesh:
        params, opt = make_train_state(cell, jax.random.PRNGKey(0))
        if mgr and resume and mgr.latest_step() is not None:
            start, state = mgr.restore(
                {"params": params, "opt": opt},
                shardings={"params": cell.in_shardings[0],
                           "opt": cell.in_shardings[1]})
            params, opt = state["params"], state["opt"]
            print(f"[train] restored from step {start}")

        losses = []
        try:
            for step in range(start, steps):
                if fail_at_step is not None and step == fail_at_step:
                    raise RuntimeError(f"injected failure at step {step}")
                t0 = time.perf_counter()
                batch_arrs = pipe.get_batch(step)
                params, opt, metrics = step_fn(params, opt, batch_arrs)
                loss = float(metrics["loss"])
                dt = time.perf_counter() - t0
                coord.straggle.record("host0", dt)
                coord.hb.beat("host0")
                losses.append(loss)
                if step % log_every == 0:
                    print(f"[train] step={step} loss={loss:.4f} "
                          f"gnorm={float(metrics['grad_norm']):.3f} "
                          f"lr={float(metrics['lr']):.2e} {dt * 1e3:.0f}ms")
                if mgr and (step + 1) % ckpt_every == 0:
                    mgr.save_async(step + 1, {"params": params, "opt": opt})
        finally:
            # a training-step failure must not kill an in-flight async
            # save: flush it so restart sees the last issued checkpoint
            if mgr:
                mgr.wait()
        if mgr:
            mgr.save(steps, {"params": params, "opt": opt})
    return losses


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--full", dest="reduced", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--fail-at-step", type=int, default=None)
    args = ap.parse_args()
    losses = train(args.arch, steps=args.steps, batch=args.batch,
                   seq=args.seq, reduced=args.reduced,
                   ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                   fail_at_step=args.fail_at_step)
    print(f"[train] done; loss {losses[0]:.4f} -> {losses[-1]:.4f}")


if __name__ == "__main__":
    main()
