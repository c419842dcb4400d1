"""The sweep `rl/scst.py::_ROW_BLOCK_CAP` is set from: the RL update program
alone, on the chip, at preset 4's widths (B=1792, K=5, `update_chunks`=5,
donated and guarded as the Trainer builds it), once for each row cap given.

    python scripts/update_row_sweep.py 1792 896 448 256 224

For each cap one JSON line: the block the shape rule chose, seconds to
compile, the compiler's temporaries, the memory space of the backward scan's
two bank-cotangent accumulators in the compiled text (`S(1)` on a layout is
the chip's fast memory; none is HBM), and the milliseconds of one update:
the host clock around RUNS executions enqueued back to back and waited for
once, so the device is never idle between them. The lines are also written
to `chiprun_out/update_row_sweep.jsonl`. Exits 1 without a TPU: a CPU gives
no time worth the name. The cap is a private constant of the program and
only this script sets it, to measure; nothing a user runs does.
"""

import json
import os
import re
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from cst_captioning_tpu.config import get_preset  # noqa: E402
from cst_captioning_tpu.models import CaptionModel  # noqa: E402
from cst_captioning_tpu.rl import scst  # noqa: E402
from cst_captioning_tpu.train.schedule import make_optimizer  # noqa: E402
from cst_captioning_tpu.train.state import create_train_state  # noqa: E402

B, K, CHUNKS, RUNS = 1792, 5, 5, 10
# the two accumulators by what they accumulate (the fusions' numbers change
# from one program to the next, the op_name does not)
ACCUMULATORS = {
    "memory": ("bm,bme->be/add_any", 512),
    "memory_proj": ("attention/add_any", 256),
}


def accumulator_spaces(text: str) -> dict:
    """-> {"memory": "fast" | "hbm", "memory_proj": ...} from the compiled
    text: the first output of the `select_add_fusion` whose op_name ends in
    the accumulation and whose shape is bf16 [rows, slots, width]."""
    found = {}
    for line in text.splitlines():
        m = re.match(r"\s*%?select_add_fusion[.\d]* = \((bf16\[\d+,\d+,(\d+)\]"
                     r"\{[^}]*\})", line)
        if not m:
            continue
        for name, (op, width) in ACCUMULATORS.items():
            if int(m.group(2)) == width and op + '"' in line:
                found[name] = "fast" if "S(1)" in m.group(1) else "hbm"
    return found


def main(caps):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU here ({dev.platform}): nothing to measure")
        return 1
    cfg = get_preset("msrvtt_cst_consensus")
    mc = cfg.model
    model = CaptionModel(mc)
    rng = np.random.default_rng(0)
    feats = {n: jnp.asarray(rng.normal(size=(B, mc.max_frames, d)), jnp.float32)
             for n, d in mc.modalities}
    masks = {n: jnp.ones((B, mc.max_frames), jnp.float32)
             for n, _ in mc.modalities}
    lens = rng.integers(4, 18, size=(K, B, 1))
    samples = rng.integers(4, mc.vocab_size, size=(K, B, mc.max_len))
    samples = jnp.asarray(
        np.where(np.arange(mc.max_len) < lens, samples, 0), jnp.int32
    )
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    tx = make_optimizer(cfg.train, steps_per_epoch=4)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/update_row_sweep.jsonl", "w")
    for cap in caps:
        scst._ROW_BLOCK_CAP = cap
        state = create_train_state(
            model, tx, (feats, masks, samples[0]), seed=1
        )
        update = scst.make_rl_update(model, chunks=CHUNKS, donate=True,
                                     guard=True)
        t0 = time.perf_counter()
        compiled = update.lower(
            state, feats, masks, samples, adv, valid
        ).compile()
        compile_s = time.perf_counter() - t0
        for _ in range(2):
            state, metrics = compiled(state, feats, masks, samples, adv, valid)
        jax.block_until_ready(state)
        t0 = time.perf_counter()
        for _ in range(RUNS):
            state, metrics = compiled(state, feats, masks, samples, adv, valid)
        jax.block_until_ready(state)
        ms = (time.perf_counter() - t0) / RUNS * 1e3
        line = {
            "cap": cap,
            "block_rows": scst._row_block(B, cap),
            "update_ms": round(ms, 3),
            "compile_s": round(compile_s, 1),
            "temp_gb": round(
                compiled.memory_analysis().temp_size_in_bytes / 1e9, 3
            ),
            "accumulators": accumulator_spaces(compiled.as_text()),
            "rl_loss": float(metrics["rl_loss"]),
            "device": dev.device_kind,
        }
        print(json.dumps(line), flush=True)
        out.write(json.dumps(line) + "\n")
        out.flush()
        del state, compiled, update
    return 0


if __name__ == "__main__":
    sys.exit(main([int(c) for c in sys.argv[1:]] or [1792, 896, 448, 256, 224]))
