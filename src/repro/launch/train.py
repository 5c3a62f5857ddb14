"""Training launcher.

Local-scale entry point: trains a reduced (smoke) variant of any assigned
arch on synthetic data with the fault-tolerant trainer.  At fleet scale the
same builders run under the production mesh (see dryrun.py for the lowering
path and DESIGN.md §5 for the mesh/sharding layout).

    PYTHONPATH=src python -m repro.launch.train --arch mixtral-8x7b \
        --steps 50 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_bundle, smoke_config
from repro.data.loader import ShardedBatcher
from repro.launch.compile_cache import enable_compile_cache
from repro.models import gnn, recsys, transformer
from repro.training.optimizer import adamw
from repro.training.trainer import Trainer, TrainerConfig


def synth_batches(arch, cfg, global_batch, seed=0):
    rng = np.random.default_rng(seed)
    fam = get_bundle(arch).family
    n = global_batch * 8
    if fam in ("lm", "gr"):
        data = {"tokens": rng.integers(0, cfg.vocab_size, (n, 33)).astype(np.int32)}
    elif fam == "recsys":
        data = {
            "sparse": np.stack(
                [rng.integers(0, v, (n, cfg.multi_hot)) for v in cfg.vocab_sizes],
                axis=1).astype(np.int32),
            "dense": rng.normal(size=(n, max(cfg.n_dense, 1))).astype(np.float32),
            "hist": rng.integers(0, 40, (n, cfg.hist_len)).astype(np.int32),
            "target": rng.integers(0, 40, (n,)).astype(np.int32),
            "label": rng.integers(0, 2, (n,)).astype(np.float32),
        }
    else:  # gnn: batched small graphs
        N, E = 24, 48
        data = {
            "node_feats": rng.normal(size=(n, N, cfg.node_feat_dim)).astype(np.float32),
            "edge_feats": rng.normal(size=(n, E, cfg.edge_feat_dim)).astype(np.float32),
            "senders": rng.integers(0, N, (n, E)).astype(np.int32),
            "receivers": rng.integers(0, N, (n, E)).astype(np.int32),
            "targets": rng.normal(size=(n, N, cfg.out_dim)).astype(np.float32),
        }
    return ShardedBatcher(data, global_batch, seed=seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--grad-compression", action="store_true")
    ap.add_argument("--resume", action="store_true")
    args = ap.parse_args()

    enable_compile_cache()
    fam = get_bundle(args.arch).family
    cfg = smoke_config(args.arch)
    key = jax.random.key(0)
    if fam in ("lm", "gr"):
        params = transformer.init_params(cfg, key)
        loss = lambda p, b: transformer.lm_loss(p, b["tokens"], cfg)
    elif fam == "recsys":
        params = recsys.init_params(cfg, key)
        loss = lambda p, b: recsys.recsys_loss(p, b, cfg)
    else:
        params = gnn.init_params(cfg, key)
        loss = lambda p, b: gnn.gnn_loss(p, b, cfg)

    trainer = Trainer(
        loss, adamw(lr=1e-3), params,
        TrainerConfig(
            n_steps=args.steps, microbatches=args.microbatches,
            ckpt_dir=args.ckpt_dir, ckpt_every=max(args.steps // 3, 1),
            grad_compression=args.grad_compression, log_every=5,
        ),
    )
    batches = synth_batches(args.arch, cfg, args.batch)
    if args.resume and trainer.resume():
        print(f"resumed from step {trainer.step}")
    losses = trainer.fit(batches)
    print(f"done: {trainer.step} steps, final loss {losses[-1]:.4f}, "
          f"stragglers: {trainer.straggler_events}")


if __name__ == "__main__":
    main()
