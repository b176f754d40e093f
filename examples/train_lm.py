"""End-to-end training driver: raw CSV bytes → ParPaRaw on-device parse →
byte-token batches → sharded train step with checkpointing + fault tolerance.

Default invocation trains a small qwen2-family model for a few hundred steps
on this CPU host; ``--arch/--size 100m`` scales to the ~100M-parameter
configuration (same code path, longer wall-clock):

    PYTHONPATH=src python examples/train_lm.py --steps 200
    PYTHONPATH=src python examples/train_lm.py --size 100m --steps 300
"""
import argparse
import dataclasses
import sys

sys.path.insert(0, "src")

import jax
import numpy as np

from repro import compile_cache
from repro.configs import get_config
from repro.configs.base import ModelConfig
from repro.core import Schema
from repro.data import synth
from repro.data.pipeline import CSVTokenPipeline, PipelineConfig
from repro.models.model import build_model
from repro.train import optimizer as opt_mod
from repro.train.checkpoint import CheckpointManager
from repro.train.loop import FailureInjector, run_training
from repro.train.train_step import TrainConfig, init_train_state, make_train_step

SIZES = {
    # byte-vocab variants of the qwen2 family
    "tiny": dict(n_layers=2, d_model=128, n_heads=4, n_kv_heads=2, d_ff=512),
    "20m": dict(n_layers=6, d_model=512, n_heads=8, n_kv_heads=2, d_ff=1536),
    "100m": dict(n_layers=12, d_model=768, n_heads=12, n_kv_heads=4, d_ff=3072),
}


def main():
    compile_cache.enable()
    ap = argparse.ArgumentParser()
    ap.add_argument("--size", default="tiny", choices=list(SIZES))
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--records", type=int, default=20000)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--fail-at", type=int, default=None,
                    help="inject a failure at this step (fault-tolerance demo)")
    ap.add_argument("--microbatches", type=int, default=1)
    args = ap.parse_args()

    cfg = ModelConfig(
        name=f"bytelm-{args.size}", family="dense", vocab=512,
        qkv_bias=True, tie_embeddings=True, remat=False,
        param_dtype=jax.numpy.float32, **SIZES[args.size],
    )
    model = build_model(cfg)
    print(f"model: {cfg.name}  params≈{cfg.param_count()/1e6:.1f}M")

    # --- data: ParPaRaw-parsed synthetic yelp CSV -> byte tokens ------------
    data = synth.yelp_like(np.random.default_rng(0), args.records)
    schema = Schema.of(*synth.YELP_SCHEMA)
    pipe = CSVTokenPipeline(schema, PipelineConfig(
        seq_len=args.seq_len, batch_size=args.batch,
        partition_bytes=1 << 18, max_carry_bytes=1 << 16,
    ))

    def data_factory(start_step):
        def forever():
            while True:
                yield from pipe.batches([data], start_step=0)
        it = forever()
        for _ in range(start_step):
            next(it)
        return it

    # --- training ------------------------------------------------------------
    ocfg = opt_mod.OptimizerConfig(lr=3e-3, warmup_steps=20, total_steps=args.steps)
    opt = opt_mod.make_optimizer(ocfg)
    state = init_train_state(model, jax.random.PRNGKey(0), opt)
    tc = TrainConfig(optimizer=ocfg, microbatches=args.microbatches)
    step_fn = jax.jit(make_train_step(model, opt, tc), donate_argnums=(0,))
    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    injector = FailureInjector(args.fail_at) if args.fail_at else None

    state, hist = run_training(
        step_fn, state, data_factory, total_steps=args.steps,
        ckpt=ckpt, ckpt_every=50, log_every=10, injector=injector,
    )
    first, last = hist[0]["loss"], hist[-1]["loss"]
    print(f"loss {first:.3f} -> {last:.3f} over {args.steps} steps "
          f"({'improved' if last < first else 'NOT improved'})")


if __name__ == "__main__":
    main()
