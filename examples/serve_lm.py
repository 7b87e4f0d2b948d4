"""Serve a small LM with batched requests: prefill + KV-cache decode.

    PYTHONPATH=src python examples/serve_lm.py [--batch 8 --new-tokens 24]

Exercises the serving substrate used by the decode_32k / long_500k dry-run
cells (prefill step, per-token decode step, batched greedy/temperature
sampling) at CPU-friendly scale.
"""
import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config, reduced
from repro.models import lm
from repro.serve import ServeEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = dataclasses.replace(reduced(get_config(args.arch)), n_layers=4)
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params,
                         max_seq=args.prompt_len + args.new_tokens)

    prompts = jnp.asarray(
        np.random.default_rng(0).integers(
            2, cfg.vocab_size, size=(args.batch, args.prompt_len)
        ),
        jnp.int32,
    )
    # warmup (compile prefill + decode)
    engine.generate(prompts, max_new_tokens=2)

    t0 = time.time()
    out = engine.generate(
        prompts, max_new_tokens=args.new_tokens,
        temperature=args.temperature, key=jax.random.PRNGKey(1),
    )
    dt = time.time() - t0
    n_new = args.batch * args.new_tokens
    print(f"arch={cfg.name} (reduced)  batch={args.batch}  "
          f"prompt={args.prompt_len}  new={args.new_tokens}")
    print(f"generated {n_new} tokens in {dt:.2f}s → {n_new/dt:.1f} tok/s")
    for i in range(min(3, args.batch)):
        print(f"  seq{i}: {np.asarray(out[i, args.prompt_len:]).tolist()}")


if __name__ == "__main__":
    main()
