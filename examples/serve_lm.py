"""Serve a small LM with batched requests: prefill + jitted decode loop.

    PYTHONPATH=src python examples/serve_lm.py --arch gemma3-1b --reduced
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models.model import init_model
from repro.serving import Engine, ServeConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma3-1b")
    ap.add_argument("--reduced", action="store_true", default=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.8)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch, reduced=args.reduced)
    params, _ = init_model(cfg, jax.random.key(0), jnp.float32)
    n = sum(x.size for x in jax.tree.leaves(params))
    print(f"serving {cfg.name}: {n/1e6:.1f}M params, batch={args.batch}")

    eng = Engine(
        cfg, params,
        ServeConfig(
            batch=args.batch,
            capacity=args.prompt_len + args.max_new + 8,
            temperature=args.temperature,
        ),
    )
    prompts = jax.random.randint(
        jax.random.key(1), (args.batch, args.prompt_len), 0, cfg.vocab_size
    )
    out = eng.generate(prompts, max_new=args.max_new)  # compile + warm
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    total_new = args.batch * args.max_new
    print(f"generated {total_new} tokens in {dt:.2f}s "
          f"({total_new/dt:.1f} tok/s batched)")
    print("sample continuation token ids:", np.asarray(out[0, args.prompt_len:]))
    assert out.shape == (args.batch, args.prompt_len + args.max_new)


if __name__ == "__main__":
    main()
