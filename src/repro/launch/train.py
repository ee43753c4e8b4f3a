"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b --smoke \\
      --steps 200 --seq 128 --batch 8

Runs on whatever devices exist (CPU smoke / real TPU pod); checkpointing,
deterministic resume and (optionally) int8-compressed gradient sync are on.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from ..configs import ARCHS, SMOKE
from ..data.pipeline import PipelineConfig, TokenPipeline
from ..distributed import sharding as SH
from ..launch.steps import make_train_step
from ..optim import adamw
from ..runtime.checkpoint import CheckpointManager


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-friendly)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    cfg = (SMOKE if args.smoke else ARCHS)[args.arch]
    n_dev = len(jax.devices())
    mp = args.model_parallel
    mesh = jax.make_mesh((n_dev // mp, mp), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    print(f"arch={cfg.name} devices={n_dev} mesh=({n_dev // mp},{mp})")

    model, step, p_shapes, p_specs, opt_shapes, o_specs = \
        make_train_step(cfg, mesh, compress_grads=args.compress_grads)
    params = jax.device_put(model.init(jax.random.key(0)),
                            SH.to_named(mesh, p_specs))
    opt = jax.device_put(
        adamw.init(params, compress=args.compress_grads),
        jax.tree.map(lambda s: NamedSharding(mesh, s), o_specs,
                     is_leaf=lambda s: isinstance(s, P)))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"params: {n_params / 1e6:.1f}M")

    mgr = CheckpointManager(args.ckpt_dir)
    start = 0
    if mgr.latest_step() is not None:
        start, restored = mgr.restore_tree({"params": params, "opt": opt})
        params, opt = restored["params"], restored["opt"]
        print(f"resumed from step {start}")

    pipe = TokenPipeline(PipelineConfig(cfg.vocab, args.seq, args.batch))
    jstep = jax.jit(step, donate_argnums=(0, 1))
    t0 = time.time()
    for i in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in pipe.batch_at(i).items()}
        params, opt, metrics = jstep(params, opt, batch)
        if (i + 1) % args.log_every == 0:
            loss = float(metrics["loss"])
            dt = (time.time() - t0) / args.log_every
            tok_s = args.seq * args.batch / dt
            print(f"step {i + 1:5d} loss {loss:.4f} "
                  f"{dt * 1e3:.0f} ms/step {tok_s:.0f} tok/s", flush=True)
            t0 = time.time()
        if (i + 1) % args.ckpt_every == 0:
            mgr.save(i + 1, {"params": params, "opt": opt})
    mgr.save(args.steps, {"params": params, "opt": opt}, blocking=True)
    print("done")


if __name__ == "__main__":
    main()
