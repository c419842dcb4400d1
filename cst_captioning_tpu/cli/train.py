"""Training CLI: XE phase and/or CST-RL phase per the preset.

Reference equivalent: ``python train.py --feats resnet c3d --loss xe ...``
driven by Makefile recipes (SURVEY.md §3.1). The two-stage paper recipe is

    # stage 1: cross-entropy
    python -m cst_captioning_tpu.cli.train --preset msrvtt_xe_attention ...
    # stage 2: CST fine-tune from the best XE checkpoint
    python -m cst_captioning_tpu.cli.train --preset msrvtt_cst_consensus \\
        --set rl__init_from=checkpoints/msrvtt_xe_attention ...
"""

from __future__ import annotations

import argparse

from cst_captioning_tpu.cli.common import add_common_args, load_config, open_dataset
from cst_captioning_tpu.train import multihost
from cst_captioning_tpu.train.trainer import Trainer
from cst_captioning_tpu.utils.compile_cache import enable_compile_cache


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    add_common_args(p)
    p.add_argument("--skip-xe", action="store_true", help="run only the RL phase")
    args = p.parse_args(argv)
    enable_compile_cache()
    # multi-host: no-op unless JAX_COORDINATOR_ADDRESS etc. are set
    multihost.initialize()
    if args.log_jsonl and multihost.is_multiprocess():
        import jax

        if jax.process_index() != 0:
            # one JSONL per process: append-interleaving on a shared path
            # would corrupt per-epoch analysis
            args.log_jsonl = f"{args.log_jsonl}.proc{jax.process_index()}"

    cfg = load_config(args)
    train_ds = open_dataset(args, cfg, "train")
    try:
        val_ds = open_dataset(args, cfg, "val")
    except ValueError as e:
        # only a genuinely absent val split is optional; every other dataset
        # error (dim mismatch, missing h5 keys, ...) must surface
        if "no videos for split" not in str(e):
            raise
        val_ds = None

    # the Trainer configures the obs recorder from cfg.train.obs; the CLI
    # owns finalization so a crashed/finished run still gets its trace.json
    # + final metrics snapshot (obs.shutdown is a no-op when obs is off)
    from cst_captioning_tpu import obs

    try:
        trainer = Trainer(cfg, train_ds, val_ds, log_path=args.log_jsonl)
        if not args.skip_xe:
            trainer.train_xe()
        if cfg.rl.enabled:
            if cfg.rl.init_from:
                trainer.load_params_from(cfg.rl.init_from, "best")
            trainer.train_rl()
    finally:
        obs.shutdown()


if __name__ == "__main__":
    main()
