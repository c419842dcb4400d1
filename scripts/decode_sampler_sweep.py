"""What `decoding/sample.py`'s token selection costs: the RL decode program
alone, on the chip, at the `cst` cells' shapes (preset `msrvtt_cst_consensus`,
K=5, the benchmark's warm-started policy and the first clips of its corpus,
`make_rl_decode(..., with_greedy=False)`), under three selections:

    python scripts/decode_sampler_sweep.py            # B = 1792 and 448
    python scripts/decode_sampler_sweep.py 1792

- `gumbel`: what `sample_decode` ran until PR 39, Gumbel-max over the
  vocabulary (`argmax(tl + gumbel_step_noise(...))`): K x B x V draws a step.
- `none`: `argmax(tl)`, no draw at all: what the program costs without its
  sampler. A lower bound and not a candidate (every rollout is the greedy
  caption).
- `inverse_cdf`: the program as it stands (`common.sample_lanes`): K x B
  draws a step.

For each batch and selection one JSON line: seconds to compile, the
compiler's temporaries, the milliseconds of one decode (the host clock
around RUNS executions enqueued back to back and waited for once, so the
device is never idle between them), the steps its loop ran (a whole number
of exit strides: `none` decodes the greedy caption K times and may leave its
loop earlier, so compare `ms_per_step`), the mean sampled caption length, and
whether the threefry rounds sit inside the reduce that selects
(`rounds_in_select`: the fusion that holds the `[K, B, V]` reduce also holds
a shift-right-logical of that shape). The lines are also written to
`chiprun_out/decode_sampler_sweep.jsonl`. The policy and the corpus come from
the benchmark's cache (`benchmark/.cache`), made on the first run of a
checkout (about two minutes). Exits 1 without a TPU: a CPU gives no time
worth the name. The selection is a private seam of the program
(`sample.sample_lanes`) and only this script sets it, to measure; nothing a
user runs does.
"""

import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import run as bench_run, training  # noqa: E402
from cst_captioning_tpu.ckpt import load_params  # noqa: E402
from cst_captioning_tpu.config import get_preset  # noqa: E402
from cst_captioning_tpu.data.batcher import Batcher  # noqa: E402
from cst_captioning_tpu.decoding import common, sample  # noqa: E402
from cst_captioning_tpu.decoding.common import _exit_stride  # noqa: E402
from cst_captioning_tpu.models import CaptionModel  # noqa: E402
from cst_captioning_tpu.rl.scst import make_rl_decode  # noqa: E402
from cst_captioning_tpu.utils.compile_cache import enable_compile_cache  # noqa: E402

CELL, K, RUNS = "msrvtt_attention.cst_b1792", 5, 10


def gumbel(step_keys_t, tl):
    noise = common.gumbel_step_noise(step_keys_t, tl.shape[1:], tl.dtype)
    return jnp.argmax(tl + noise, axis=-1).astype(jnp.int32)


def none(step_keys_t, tl):
    return jnp.argmax(tl, axis=-1).astype(jnp.int32)


VARIANTS = {"gumbel": gumbel, "none": none,
            "inverse_cdf": common.sample_lanes}


def rounds_in_select(text: str, lanes: str) -> bool:
    """Does a fused computation of the compiled text hold both a reduce of
    a ``[K, B, V]`` operand and threefry's shifts of that shape."""
    for body in re.split(r"\n(?=%?fused_computation)", text):
        if (re.search(rf"\[{lanes}\][^ ]* shift-right-logical\(", body)
                and re.search(r" reduce\(", body)):
            return True
    return False


def main(batches):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing to measure")
        return 1
    enable_compile_cache()
    _, _, _, config = bench_run.load_cell(CELL)
    paths = training.ensure_corpus(bench_run.CACHE_DIR, config["corpus"])
    policy_dir = training.ensure_policy(bench_run.CACHE_DIR, config, paths,
                                        bench_run.log)
    cfg = get_preset("msrvtt_cst_consensus").override(
        data__cache_features=True)
    model = CaptionModel(cfg.model)
    ds = training.open_train_split(cfg, paths)
    first = next(iter(Batcher(ds, batch_size=max(batches),
                              max_len=cfg.model.max_len,
                              mode="video").epoch(shuffle=False)))
    template = model.init(
        jax.random.key(0),
        {n: jnp.zeros((2, cfg.model.max_frames, d))
         for n, d in cfg.model.modalities},
        {n: jnp.ones((2, cfg.model.max_frames))
         for n, _ in cfg.model.modalities},
        jnp.zeros((2, cfg.model.max_len), jnp.int32),
    )
    params = jax.device_put(
        load_params(policy_dir, "latest", jax.device_get(template)))
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/decode_sampler_sweep.jsonl", "w")
    stride = _exit_stride(cfg.model.max_len)
    for B in batches:
        feats = {n: jnp.asarray(v[:B]) for n, v in first.feats.items()}
        masks = {n: jnp.asarray(v[:B]) for n, v in first.feat_masks.items()}
        for name, select in VARIANTS.items():
            sample.sample_lanes = select
            decode = make_rl_decode(model, K, cfg.rl.temperature,
                                    with_greedy=False)
            t0 = time.perf_counter()
            compiled = decode.lower(params, feats, masks,
                                    jax.random.key(0)).compile()
            compile_s = time.perf_counter() - t0
            keys = [jax.random.key(i) for i in range(RUNS + 2)]
            for key in keys[:2]:
                _, samples = compiled(params, feats, masks, key)
            jax.block_until_ready(samples)
            t0 = time.perf_counter()
            timed = [compiled(params, feats, masks, key)[1]
                     for key in keys[2:]]
            jax.block_until_ready(timed)
            ms = (time.perf_counter() - t0) / RUNS * 1e3
            lens = (np.asarray(timed) != 0).sum(-1)     # [RUNS, K, B]
            steps = float(np.mean(
                -(-lens.max(axis=(1, 2)) // stride) * stride))
            line = {
                "B": B, "K": K, "selection": name,
                "decode_ms": round(ms, 3),
                # the mean over the timed decodes, each under a key of its own
                "steps_run": steps,
                "ms_per_step": round(ms / steps, 3),
                "sampled_len_mean": round(float(lens.mean()), 3),
                "compile_s": round(compile_s, 1),
                "temp_gb": round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e9, 3),
                "rounds_in_select": rounds_in_select(
                    compiled.as_text(), f"{K},{B},{cfg.model.vocab_size}"),
                "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
            del compiled, decode
    sample.sample_lanes = common.sample_lanes
    ds.close()
    return 0


if __name__ == "__main__":
    sys.exit(main([int(b) for b in sys.argv[1:]] or [1792, 448]))
