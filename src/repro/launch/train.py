"""Training launcher.

CPU-runnable end-to-end with ``--reduced`` (the smoke/example path); with
``--production`` it builds the full config + production mesh shardings and
requires a real pod (or the dry-run, which is the compile-only variant).

  PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b --reduced \
      --steps 50 --batch 8 --seq 128
"""

from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp

from repro.configs.registry import get_config, reduced_config
from repro.data.pipeline import DataConfig, SyntheticPipeline
from repro.launch.compile_cache import enable_compile_cache
from repro.models.layers import WarpFeatureConfig
from repro.models.lm import Model
from repro.optim.optimizer import AdamWConfig
from repro.train.trainer import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--warp-backend", default="auto",
                    choices=["auto", "hw", "sw", "pallas"],
                    help="rmsnorm reduction lowering (auto: pallas on "
                         "TPU, hw elsewhere)")
    ap.add_argument("--attn-backend", default="auto",
                    choices=["auto", "kernel", "jnp"],
                    help="training attention lowering (auto: flash "
                         "Pallas kernel on TPU, chunked jnp elsewhere)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    wf = WarpFeatureConfig(
        reduction_backend=None if args.warp_backend == "auto"
        else args.warp_backend)
    model = Model(cfg, wf=wf, compute_dtype=jnp.float32,
                  attn_backend=None if args.attn_backend == "auto"
                  else args.attn_backend)
    data = SyntheticPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, n_frontend_tokens=cfg.n_frontend_tokens,
        d_model=cfg.d_model))
    opt = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps)
    trainer = Trainer(model, data, opt, TrainerConfig(
        total_steps=args.steps, checkpoint_dir=args.ckpt_dir,
        checkpoint_every=args.ckpt_every, accum_steps=args.accum,
        vocab_chunks=4))

    def log(step, m):
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step:5d}  loss {m['loss']:.4f}  "
                  f"lr {m['lr']:.2e}  gnorm {m['grad_norm']:.3f}  "
                  f"{m['step_time_s'] * 1e3:.0f} ms", flush=True)

    state, history = trainer.run(jax.random.PRNGKey(args.seed),
                                 on_metrics=log)
    first, last = history[0][1]["loss"], history[-1][1]["loss"]
    print(f"loss {first:.4f} -> {last:.4f} over {len(history)} steps "
          f"({cfg.name}, {sum(x.size for x in jax.tree.leaves(state.params)):,}"
          f" params)")
    if trainer.straggler_events:
        print(f"straggler events: {len(trainer.straggler_events)}")


if __name__ == "__main__":
    main()
