"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        --steps 100 --batch 8 --seq 256 [--reduced] [--structure blast50] \
        [--ckpt /tmp/ckpt]

On this CPU container you run the ``--reduced`` configs (same code path as
production); on a real pod the same entry point builds the production mesh
and shards via launch/sharding.py (the dry-run proves those cells compile).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro import configs
from repro.data import TokenStream
from repro.launch.compile_cache import enable_compile_cache
from repro.models import build_model
from repro.optim import adamw, cosine_schedule
from repro.parallel import NO_PARALLEL
from repro.train import Trainer


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--structure", default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-family config")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get(args.arch, args.structure)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, NO_PARALLEL)
    opt = adamw(cosine_schedule(args.lr, args.steps, args.warmup))

    class _Data:
        stream = TokenStream(vocab=cfg.vocab, seq_len=args.seq,
                             global_batch=args.batch, seed=args.seed)

        def batch(self, step):
            b = self.stream.batch(step)
            if cfg.embeds_input and cfg.encoder is None:
                key = jax.random.fold_in(jax.random.PRNGKey(7), step)
                b["embeds"] = jax.random.normal(
                    key, (args.batch, args.seq, cfg.d_model))
            if cfg.encoder is not None:
                key = jax.random.fold_in(jax.random.PRNGKey(7), step)
                b["frames"] = jax.random.normal(
                    key, (args.batch, cfg.encoder.n_frames, cfg.d_model))
            return b

    trainer = Trainer(model, opt, _Data(), checkpoint_dir=args.ckpt,
                      checkpoint_every=args.ckpt_every,
                      microbatch=args.microbatch)
    result = trainer.run(args.steps, key=jax.random.PRNGKey(args.seed))
    hist = result["history"]
    print(f"[train] {args.arch} ({cfg.structure.kind}): "
          f"loss {hist[0]:.4f} → {hist[-1]:.4f} over {len(hist)} steps")


if __name__ == "__main__":
    main()
