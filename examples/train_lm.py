"""Train a reduced assigned-architecture LM end to end on this host.

    PYTHONPATH=src python examples/train_lm.py --arch qwen3-1.7b --steps 100

Demonstrates the non-LDA half of the framework: config resolution,
model construction, the jitted train step (loss+grad+AdamW), the
deterministic data pipeline, periodic checkpointing and restart.
Full-scale cells run the same code path on the production mesh
(see launch/train.py and launch/dryrun.py).
"""
import argparse
import tempfile

import jax

from repro.configs import ARCHS
from repro.data.lm import batch_stream
from repro.distributed.sharding import single_device_env
from repro.models.model import build_model
from repro.train.optim import OptimizerConfig
from repro.train.trainer import Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m", choices=sorted(ARCHS))
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    cfg = ARCHS[args.arch].reduced()
    model = build_model(cfg)
    env = single_device_env()
    print(f"{cfg.name}: {model.param_count():,} params "
          f"({cfg.family}, {cfg.n_layers}L d={cfg.d_model})")

    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(model, OptimizerConfig(lr=3e-3, warmup_steps=10),
                          env, ckpt_dir=ckpt_dir, save_every=25,
                          remat=False)
        state = trainer.restore_or_init()
        stream = batch_stream(cfg, args.batch, args.seq, seed=0)
        state = trainer.fit(state, stream, args.steps, log_every=10)

        # simulate preemption: restore from the checkpoint and continue
        trainer2 = Trainer(model, OptimizerConfig(lr=3e-3, warmup_steps=10),
                           env, ckpt_dir=ckpt_dir, remat=False)
        state2 = trainer2.restore_or_init()
        print(f"restart: resumed at step {int(state2.step)} "
              f"(cursor {state2.data_cursor}) — continuing 10 more")
        stream2 = batch_stream(cfg, args.batch, args.seq, seed=0,
                               start_cursor=state2.data_cursor)
        trainer2.fit(state2, stream2, 10, log_every=5)


if __name__ == "__main__":
    from repro.compile_cache import use_compile_cache
    use_compile_cache()
    main()
