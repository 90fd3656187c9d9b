"""Production training launcher.

    python -m repro.launch.train --arch <id> [--smoke] [--steps N]
           [--mesh-data D --mesh-model M] [--ckpt-dir DIR] [--microbatches K]

On this CPU host it runs the smoke config end-to-end (real optimization); on
a TPU fleet the same driver runs the full config under the production mesh —
the sharding annotations, checkpointing, fault handling and data pipeline are
identical code paths (see repro.launch.dryrun for the compile-only proof at
512 chips).
"""

from __future__ import annotations

import argparse
import time


import jax
import jax.numpy as jnp

from repro import compile_cache
from repro.configs.base import get_arch, get_smoke
from repro.dist.fault import StragglerMonitor
from repro.dist.sharding import default_rules, use_sharding
from repro.launch.mesh import make_debug_mesh
from repro.train.checkpoint import AsyncCheckpointer, latest_step, restore_checkpoint
from repro.train.data import DataConfig, SyntheticLM
from repro.train.optimizer import OptimizerConfig
from repro.train.train_step import create_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--global-batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh-data", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=0)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    opt_cfg = OptimizerConfig(
        lr=args.lr, warmup_steps=max(args.steps // 10, 1), total_steps=args.steps
    )
    mesh = None
    if args.mesh_data and args.mesh_model:
        mesh = make_debug_mesh(args.mesh_data, args.mesh_model)

    state = create_train_state(cfg, opt_cfg, jax.random.key(0))
    data = SyntheticLM(
        DataConfig(
            vocab_size=cfg.vocab_size,
            seq_len=args.seq_len,
            global_batch=args.global_batch,
        )
    )
    step_fn = jax.jit(make_train_step(cfg, opt_cfg, args.microbatches))
    ckpt = AsyncCheckpointer(args.ckpt_dir) if args.ckpt_dir else None
    monitor = StragglerMonitor()

    start = 0
    if ckpt and latest_step(args.ckpt_dir) is not None:
        state, start, extra = restore_checkpoint(args.ckpt_dir, state)
        data.load_state_dict(extra)
        print(f"[train] resumed at step {start}")

    ctx = use_sharding(mesh, default_rules()) if mesh else None
    if ctx:
        ctx.__enter__()
    try:
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
            state, metrics = step_fn(state, batch)
            straggle = monitor.observe(step, time.perf_counter() - t0)
            if step % 10 == 0 or step == args.steps - 1:
                print(
                    f"[train] step {step:>5} loss {float(metrics['loss']):.4f} "
                    f"gnorm {float(metrics['grad_norm']):.3f}"
                    + (" STRAGGLER" if straggle else "")
                )
            if ckpt and (step + 1) % args.ckpt_every == 0:
                ckpt.save(step + 1, state, extra=data.state_dict())
    finally:
        if ctx:
            ctx.__exit__(None, None, None)
        if ckpt:
            ckpt.wait()
    print("[train] done")


if __name__ == "__main__":
    main()
