"""Training launcher: ingest a synthetic token dataset into the KV store,
then train through the network loader (virtual-clock network) with
checkpoint/restart.

    python -m repro.launch.train --arch stablelm_1_6b --batch-size 2 \\
        --seq-len 2048 --opt-state-dtype int8_factored --steps 8

``--arch`` names a config in ``repro.configs`` and trains it at its
published width; ``--smoke`` swaps in its tiny same-family config (for CPU
tests).  The default ``demo`` arch is a 4-layer float32 toy.  With
``--checkpoint-dir`` a run resumes from the latest checkpoint there and
saves one at its last step.

On a multi-host cluster, per-host data loading is configured with
``LoaderConfig(shard_id=jax.process_index(), num_shards=jax.process_count())``
so each host fetches exactly its shard of the global batch.
"""

from __future__ import annotations

import argparse
from typing import Dict, Optional, Sequence


def main(argv: Optional[Sequence[str]] = None) -> Dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="demo")
    ap.add_argument("--smoke", action="store_true",
                    help="train the arch's tiny smoke config instead")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--route", default="high")
    ap.add_argument("--out-of-order", type=int, default=1)
    ap.add_argument("--opt-state-dtype", default="float32",
                    choices=("float32", "int8", "int8_factored"))
    ap.add_argument("--checkpoint-dir", default="")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.configs.base import get_arch
    from repro.core import KVStore, LoaderConfig
    from repro.data.datasets import SyntheticTokenDataset, ingest
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import build_model
    from repro.train.loop import TrainLoopConfig, run_training
    from repro.train.optimizer import OptimizerConfig

    enable_compile_cache()
    if args.arch == "demo":
        from repro.configs.base import ArchConfig
        cfg = ArchConfig(name="demo-120m", family="dense", n_layers=4,
                         d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
                         vocab=32000, head_dim=32, dtype="float32",
                         remat=False)
    else:
        cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke_config()
    model = build_model(cfg)

    store = KVStore()
    uuids = ingest(store, SyntheticTokenDataset(
        n_samples=max(args.batch_size * 64, 2048), seq_len=args.seq_len,
        vocab=cfg.vocab, seed=args.seed))
    loader_cfg = LoaderConfig(batch_size=args.batch_size, prefetch_buffers=8,
                              io_threads=8, route=args.route,
                              out_of_order=bool(args.out_of_order),
                              materialize=True, seed=args.seed)
    loop_cfg = TrainLoopConfig(total_steps=args.steps, seq_len=args.seq_len,
                               log_every=args.log_every,
                               checkpoint_dir=args.checkpoint_dir or None,
                               seed=args.seed)
    opt_cfg = OptimizerConfig(total_steps=args.steps,
                              state_dtype=args.opt_state_dtype)
    result = run_training(model, store, uuids, loader_cfg, loop_cfg, opt_cfg,
                          on_metrics=lambda m: print(
                              f"step {m['step']:5d} loss {m['loss']:.4f} "
                              f"step_s {m['step_s']:.4f} "
                              f"{m['sps']:.0f} samples/s", flush=True))
    first, last = result["history"][0], result["history"][-1]
    print(f"{cfg.name}: loss {first['loss']:.4f} -> {last['loss']:.4f} "
          f"over steps {first['step']}..{last['step']}")
    return result


if __name__ == "__main__":
    main()
