"""Distributed training launcher.

On a pod this builds the production mesh, applies the FSDP sharding rules
and pjit-compiles the train step; on this CPU host the same code path runs
with a 1×1 mesh and a reduced config — one code path, two scales.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-0.6b \
        --reduced --steps 20
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.configs.registry import ARCH_NAMES, get_config
from repro.data.pipeline import LmTokenStream
from repro.launch.mesh import make_mesh
from repro.launch.sharding import ShardingRules
from repro.models.model import Model
from repro.train import checkpoint
from repro.train.loop import TrainConfig, make_train_step
from repro.train.optimizer import AdamWConfig, init_opt_state


def make_mesh_from_devices():
    n = jax.device_count()
    data = max(1, n // 2) if n > 1 else 1
    model_ax = n // data
    return make_mesh((data, model_ax), ("data", "model"))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-0.6b", choices=ARCH_NAMES)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-scale variant of the architecture")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--remat", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--save", default=None)
    args = ap.parse_args()

    use_compile_cache()
    name = args.arch + ("-reduced" if args.reduced else "")
    cfg = get_config(name)
    model = Model(cfg)
    mesh = make_mesh_from_devices()
    rules = ShardingRules(mesh, train=True)
    print(f"arch={cfg.name} params={cfg.param_count():,} "
          f"mesh={dict(mesh.shape)}")

    tcfg = TrainConfig(opt=AdamWConfig(lr=args.lr, warmup_steps=10,
                                       total_steps=args.steps),
                       remat=args.remat, microbatches=args.microbatches)
    step_fn = make_train_step(model, tcfg)
    stream = LmTokenStream(cfg.vocab_size, seq_len=args.seq,
                           batch_size=args.batch)

    with jax.set_mesh(mesh):
        params = jax.jit(
            lambda k: model.init(k),
            out_shardings=rules.params(jax.eval_shape(
                model.init, jax.random.PRNGKey(0))),
        )(jax.random.PRNGKey(0))
        opt_state = init_opt_state(params)
        jitted = jax.jit(step_fn)
        t0 = time.time()
        for step in range(args.steps):
            batch = {k: jnp.asarray(v)
                     for k, v in stream.batch(step).items()}
            params, opt_state, metrics = jitted(params, opt_state, batch)
            if step % 5 == 0 or step == args.steps - 1:
                print(f"step {step:4d} loss {float(metrics['loss']):.4f} "
                      f"({time.time()-t0:.1f}s)", flush=True)
    if args.save:
        checkpoint.save(args.save, params, meta={"steps": args.steps})
        print("checkpoint:", args.save)


if __name__ == "__main__":
    main()
