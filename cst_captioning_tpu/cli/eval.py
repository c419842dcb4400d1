"""Evaluation CLI: beam-search decode a split + COCO-style metric table.

Reference equivalent: ``python test.py --beam_size 5 --checkpoint ...``
(SURVEY.md §3.3, BASELINE config 5).
"""

from __future__ import annotations

import argparse
import json

import jax

from cst_captioning_tpu.cli.common import add_common_args, load_config, open_dataset
from cst_captioning_tpu.ckpt import load_params
from cst_captioning_tpu.eval.evaluator import evaluate_split
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.train.mesh import make_mesh, replicate
from cst_captioning_tpu.train.steps import batch_arrays
from cst_captioning_tpu.data.batcher import Batcher
from cst_captioning_tpu.utils.compile_cache import enable_compile_cache


def main(argv: list[str] | None = None) -> None:
    p = argparse.ArgumentParser(description=__doc__)
    add_common_args(p)
    p.add_argument("--ckpt-dir", required=True)
    p.add_argument("--ckpt-name", default="best")
    p.add_argument("--split", default="")
    p.add_argument("--results-json", default="results.json")
    args = p.parse_args(argv)
    enable_compile_cache()

    from cst_captioning_tpu import obs
    from cst_captioning_tpu.train import multihost

    multihost.initialize()  # no-op unless the JAX_* cluster env vars are set
    cfg = load_config(args)
    split = args.split or cfg.eval.split
    if cfg.train.obs:
        # standalone eval runs get their own obs stream (the Evaluator's
        # "eval" spans + prefetchless decode metrics land here); report it
        # with cli.obs_report like a training run
        obs_dir = cfg.train.obs_dir or "obs_eval"
        if jax.process_index() != 0:
            obs_dir = f"{obs_dir}/proc{jax.process_index()}"
        obs.configure(obs_dir, run=f"{cfg.name}-eval-{split}")
    ds = open_dataset(args, cfg, split)

    model = CaptionModel(cfg.model)
    # template params from a throwaway init on one batch
    sample = next(iter(
        Batcher(ds, batch_size=2, max_len=cfg.model.max_len, mode="video").epoch(False)
    ))
    feats, masks, labels, *_ = batch_arrays(sample)
    template = model.init(jax.random.key(0), feats, masks, labels)
    params = load_params(args.ckpt_dir, args.ckpt_name, template)

    # shard the decode over all visible devices; the Evaluator wrap-pads any
    # indivisible batch size up to a device multiple, so no silent fallback.
    # seq_devices>1 carries the training layout into eval: frames shard over
    # 'seq' (the long-context case where one device can't hold the frame axis)
    n_dev = cfg.mesh.num_devices or len(jax.devices())
    mesh = None
    if n_dev > 1 or cfg.mesh.seq_devices > 1:
        mesh = make_mesh(cfg.mesh.num_devices,
                         seq_devices=cfg.mesh.seq_devices,
                         mp_devices=cfg.mesh.mp_devices)
        params = replicate(mesh, params)

    # multi-host: every process computes the full result (the caption gather
    # is collective), but only process 0 writes the shared results file
    results_json = args.results_json if jax.process_index() == 0 else ""
    try:
        result = evaluate_split(
            model, params, ds, cfg.eval,
            batch_size=cfg.data.batch_size, results_json=results_json,
            mesh=mesh,
        )
    finally:
        obs.shutdown()
    if jax.process_index() == 0:
        print(json.dumps(result["metrics"], indent=2, default=float))


if __name__ == "__main__":
    main()
