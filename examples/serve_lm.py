"""Batched serving example: prefill a batch of prompts, then greedy
decode — the inference path the decode_32k / long_500k dry-run cells
lower at production scale.

    PYTHONPATH=src python examples/serve_lm.py --arch xlstm-1.3b
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import ARCHS
from repro.data.lm import make_batch
from repro.distributed.sharding import single_device_env, set_env
from repro.launch.serve import generate
from repro.models.model import build_model


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="xlstm-1.3b", choices=sorted(ARCHS))
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=24)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    model = build_model(cfg)
    env = single_device_env(profile="serve")
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, args.batch, args.prompt_len, 0, 0)
    batch.pop("labels", None)

    t0 = time.perf_counter()
    toks = generate(model, params, batch, env, steps=args.gen_len,
                    cache_len=args.prompt_len + args.gen_len)
    dt = time.perf_counter() - t0
    print(f"{cfg.name} ({cfg.family}): {toks.shape[0]}x{toks.shape[1]} "
          f"tokens in {dt:.2f}s "
          f"({args.batch*args.gen_len/dt:.1f} tok/s incl. compile)")
    for row in range(min(2, toks.shape[0])):
        print(f"  seq {row}:", toks[row, :16].tolist())


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
