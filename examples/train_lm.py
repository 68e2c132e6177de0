"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps.

Uses the full production stack — model zoo config (scaled-down yi-style
llama), Adafactor/AdamW, microbatch accumulation, fault-tolerant runner
with checkpoint-restart, deterministic data pipeline:

    PYTHONPATH=src python examples/train_lm.py --steps 300

Optionally exercises error-feedback int8 gradient compression across a
data-parallel axis (--ranks 4 --compress).
"""
import argparse
import os
import sys

if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=1)
    args_pre, _ = ap.parse_known_args()
    if args_pre.ranks > 1 and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args_pre.ranks}"
        )
        os.execv(sys.executable, [sys.executable] + sys.argv)

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.data import SyntheticLM
from repro.models.config import ModelConfig
from repro.models.model import init_model
from repro.training import (
    RunnerConfig,
    TrainRunner,
    adamw,
    make_train_step,
    warmup_cosine,
)


def build_cfg(size: str) -> ModelConfig:
    if size == "100m":
        return ModelConfig(
            name="llama-100m", family="dense", n_layers=8, d_model=512,
            n_heads=8, n_kv_heads=4, head_dim=64, d_ff=1536, vocab_size=8192,
            dtype="float32", remat=False,
        )
    return ModelConfig(
        name="llama-tiny", family="dense", n_layers=2, d_model=128,
        n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256, vocab_size=1024,
        dtype="float32", remat=False,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ranks", type=int, default=1)
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--size", default="100m", choices=["100m", "tiny"])
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train_lm")
    ap.add_argument("--compress", action="store_true",
                    help="int8+error-feedback gradient psum over the dp axis")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = build_cfg(args.size)
    params, _ = init_model(cfg, jax.random.key(0), jnp.float32)
    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"model: {cfg.name}  {n_params/1e6:.1f}M params, "
          f"{len(jax.devices())} device(s)")

    opt = adamw(b1=0.9, b2=0.95)
    opt_state = opt.init(params)
    schedule = warmup_cosine(peak_lr=3e-3, warmup=50, total=args.steps)

    if args.ranks > 1:
        # manual-DP variant: per-rank grads synced with (optionally int8)
        # psum under shard_map — the inter-pod compression path.
        from jax.sharding import PartitionSpec as P

        from repro.training.compress import ef_compressed_psum
        from repro.training.train_step import make_loss_fn
        from repro.training.optimizer import clip_by_global_norm

        from repro.compat import make_mesh, shard_map

        mesh = make_mesh((args.ranks,), ("dp",))
        loss_fn = make_loss_fn(cfg)
        grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
        residual = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params) \
            if args.compress else None

        def dp_step(params, opt_state, residual, batch, idx):
            pspec = jax.tree.map(lambda _: P(), params)

            def shard_fn(p, tokens, res):
                (_, m), g = grad_fn(p, {"tokens": tokens[0]})
                if args.compress:
                    pairs = jax.tree.map(
                        lambda gg, rr: ef_compressed_psum(gg, rr, "dp"), g, res
                    )
                    g = jax.tree.map(lambda o: o[0] / args.ranks, pairs,
                                     is_leaf=lambda x: isinstance(x, tuple))
                    res = jax.tree.map(lambda o: o[1], pairs,
                                       is_leaf=lambda x: isinstance(x, tuple))
                else:
                    g = jax.lax.pmean(g, "dp")
                m = jax.lax.pmean(m, "dp")
                return g, m, res

            res_spec = jax.tree.map(lambda _: P("dp"), residual) if args.compress else None
            fn = shard_map(
                shard_fn, mesh=mesh,
                in_specs=(pspec, P("dp"),
                          (jax.tree.map(lambda _: P("dp"), residual)
                           if args.compress else P())),
                out_specs=(pspec, P(), (res_spec if args.compress else P())),
            )
            res_in = residual if args.compress else jnp.zeros((args.ranks, 1))
            grads, metrics, res_out = fn(params, batch["tokens"][None].reshape(
                args.ranks, -1, batch["tokens"].shape[-1]), res_in)
            grads, gnorm = clip_by_global_norm(grads, 1.0)
            params, opt_state = opt.update(grads, opt_state, params,
                                           schedule(idx))
            return params, opt_state, res_out, dict(metrics, grad_norm=gnorm)

        step = jax.jit(dp_step)
        data = SyntheticLM(vocab_size=cfg.vocab_size, batch=args.batch,
                           seq_len=args.seq)
        p, s, r = params, opt_state, (residual if args.compress
                                      else jnp.zeros((args.ranks, 1)))
        t0 = time.time()
        for i in range(args.steps):
            batch = {"tokens": jnp.asarray(data(i)["tokens"])}
            p, s, r, m = step(p, s, r, batch, jnp.int32(i))
            if (i + 1) % 25 == 0:
                print(f"step {i+1}: nll={float(m['nll']):.4f}")
        print(f"done in {time.time()-t0:.1f}s "
              f"(compress={'on' if args.compress else 'off'})")
        return

    step_fn = jax.jit(make_train_step(
        cfg, opt, schedule, microbatches=args.microbatches,
    ))
    data = SyntheticLM(vocab_size=cfg.vocab_size, batch=args.batch,
                       seq_len=args.seq)

    def data_fn(i):
        return {"tokens": jnp.asarray(data(i)["tokens"])}

    runner = TrainRunner(
        RunnerConfig(
            total_steps=args.steps, checkpoint_dir=args.ckpt_dir,
            checkpoint_every=100, log_every=25,
        ),
        step_fn, data_fn, params, opt_state,
    )
    runner.try_restore()   # resume if a previous run was interrupted
    out = runner.run()
    print(f"final: {out}")


if __name__ == "__main__":
    main()
