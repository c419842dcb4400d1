"""The sweep `rl/scst.py::_ROW_BLOCK_CAP` is set from: the RL update program
alone, on the chip, at preset 4's widths (B=1792, K=5, `update_chunks`=5,
donated and guarded as the Trainer builds it), once for each row cap given
and, under each cap's one compiled program, for each depth of the samples.

    python scripts/update_row_sweep.py 1792 896 448 256 224
    python scripts/update_row_sweep.py --depth 30,20,12 448
    python scripts/update_row_sweep.py --sum product,scatter --depth 30,20 448

The depth is the longest sampled caption (of T = 30) of every rollout chunk
and row block: lengths are drawn from 4 to the depth, so each of a block's
hundreds of rows reaches it somewhere. The update's teacher forcing runs no
position past it (`models/captioner.py::teacher_force_logps`); the default,
17, is what this script's samples always were. ``--sum`` names how a block's
``[T, rows, d_embed]`` input cotangents are summed into the word embedding's
gradient after the backward loop (PR 41): ``scatter``, one scatter-add a
block, is what the program runs (`models/captioner.py::_rows_by_token`);
``product`` is the other spelling ISSUE 41 asked to be measured against it,
one product with the tokens' one-hot rows, kept here and nowhere in the
program. For each spelling, cap and depth one JSON line: the block the shape
rule chose, seconds to compile, the compiler's temporaries, the memory space
of the backward pass's two bank-cotangent accumulators in the compiled text
(`S(1)` on a layout is the chip's fast memory; none is HBM), the positions
the update reports it ran, and the milliseconds of one update: the host
clock around RUNS executions enqueued back to back and waited for once, so
the device is never idle between them. The lines are also written to
`chiprun_out/update_row_sweep.jsonl`. Exits 1 without a TPU: a CPU gives
no time worth the name. The cap is a private constant of the program and
only this script sets it, to measure; nothing a user runs does.
"""

import itertools
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
from cst_captioning_tpu.models import CaptionModel, captioner  # noqa: E402
from cst_captioning_tpu.rl import scst  # noqa: E402
from cst_captioning_tpu.train.schedule import make_optimizer  # noqa: E402
from cst_captioning_tpu.train.state import create_train_state  # noqa: E402

B, K, CHUNKS, RUNS = 1792, 5, 5, 10
# the two accumulators by the width of what they accumulate (the fusions'
# names and numbers change from one program to the next)
ACCUMULATORS = {"memory": 512, "memory_proj": 256}
# what names teacher forcing's backward pass in an op_name: the method both
# loops are traced under. The loop's own additions end in `/while/body/add`,
# the step's operations go on with `transpose(jvp(DecoderCell.step))/`
# (PR 41: the step from the embedded token; `DecoderCell` before it)
BACKWARD_OF = "transpose(jvp(CaptionModel.teacher_force_logps))"


def _product_rows_by_token(tokens, rows, table):
    """``captioner._rows_by_token``'s other spelling: the same f32 sums as
    one product ``one_hot(tokens)^T x rows`` (a one-hot is exact in any
    float dtype), 2 x T x rows x V x d operations a block."""
    hot = jax.nn.one_hot(tokens.reshape(-1), table.shape[0], dtype=rows.dtype)
    summed = jax.lax.dot_general(
        hot, rows.reshape(-1, rows.shape[-1]), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return summed.astype(table.dtype)


SUMS = {"scatter": captioner._rows_by_token, "product": _product_rows_by_token}


def accumulator_spaces(text: str, rows: int) -> dict:
    """-> {"memory": "fast" | "hbm", "memory_proj": ...} from the compiled
    text: the fusions of teacher forcing's backward pass that add into an
    operand in place and put out a bf16 [rows, slots, width] array (the
    block's ``rows``: the ``[T, rows, d_embed]`` buffer of input cotangents
    is written in place too, and is no accumulator)."""
    found = {}
    shape = re.compile(r"(bf16\[%d,\d+,(\d+)\]\{[^}]*\})" % rows)
    for line in text.splitlines():
        outputs, fusion, rest = line.partition(" fusion(")
        if not (fusion and BACKWARD_OF in rest
                and '"aliasing_operands":{"lists":[{' in rest):
            continue
        first = {}      # the first output of each width is the accumulator
        for layout, width in shape.findall(outputs):
            first.setdefault(int(width), layout)
        for name, width in ACCUMULATORS.items():
            if width in first:
                found[name] = "fast" if "S(1)" in first[width] else "hbm"
    return found


def main(caps, depths=(17,), sums=("scatter",)):
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
    tokens = rng.integers(4, mc.vocab_size, size=(K, B, mc.max_len))
    samples = {}
    for depth in depths:
        lens = rng.integers(min(4, depth), depth + 1, size=(K, B, 1))
        samples[depth] = jnp.asarray(
            np.where(np.arange(mc.max_len) < lens, tokens, 0), jnp.int32
        )
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    tx = make_optimizer(cfg.train, steps_per_epoch=4)
    os.makedirs("chiprun_out", exist_ok=True)
    out = open("chiprun_out/update_row_sweep.jsonl", "w")
    for how, cap in itertools.product(sums, caps):
        scst._ROW_BLOCK_CAP = cap
        block = scst._row_block(B, cap)
        captioner._rows_by_token = SUMS[how]
        state = create_train_state(
            model, tx, (feats, masks, samples[depths[0]][0]), seed=1
        )
        update = scst.make_rl_update(model, chunks=CHUNKS, donate=True,
                                     guard=True)
        t0 = time.perf_counter()
        compiled = update.lower(
            state, feats, masks, samples[depths[0]], adv, valid
        ).compile()
        compile_s = time.perf_counter() - t0
        for depth in depths:
            args = (feats, masks, samples[depth], adv, valid)
            for _ in range(2):
                state, metrics = compiled(state, *args)
            jax.block_until_ready(state)
            t0 = time.perf_counter()
            for _ in range(RUNS):
                state, metrics = compiled(state, *args)
            jax.block_until_ready(state)
            ms = (time.perf_counter() - t0) / RUNS * 1e3
            line = {
                "sum": how,
                "cap": cap,
                "block_rows": block,
                "depth": depth,
                "update_ms": round(ms, 3),
                "compile_s": round(compile_s, 1),
                "temp_gb": round(
                    compiled.memory_analysis().temp_size_in_bytes / 1e9, 3
                ),
                "accumulators": accumulator_spaces(compiled.as_text(), block),
                # the parent of PR 37 reports none
                "positions_run": int(metrics.get("positions_run", -1)),
                "positions": int(metrics.get("positions", -1)),
                "rl_loss": float(metrics["rl_loss"]),
                "device": dev.device_kind,
            }
            print(json.dumps(line), flush=True)
            out.write(json.dumps(line) + "\n")
            out.flush()
        del state, compiled, update
    return 0


if __name__ == "__main__":
    argv = sys.argv[1:]
    depths, sums = (17,), ("scatter",)
    while argv[:1] in (["--depth"], ["--sum"]):
        if argv[0] == "--depth":
            depths = tuple(int(d) for d in argv[1].split(","))
        else:
            sums = tuple(argv[1].split(","))
        argv = argv[2:]
    sys.exit(main([int(c) for c in argv] or [1792, 896, 448, 256, 224],
                  depths, sums))
