"""Decoupled actor/learner SCST bench: the async rollout ladder.

Round-5 ledgered the synchronous SCST loop at 3629 clips/s/chip
(round-5 record, removed in PR 21; TPU v5 lite, code older than PRs 1–20)
with the decode claiming 0.851 of the
sequential time — the learner chips idle behind the rollout. The
decoupled topology (rl/async_scst.py, ``train.rl_topology="decoupled"``)
splits the data mesh into actor and learner submeshes so decode and
update run continuously on disjoint chips; this bench measures that
ladder end to end through the real ``train_epoch``:

- ``sync``             — today's SCSTTrainer pipelined loop on the full
  mesh; the bit-exactness baseline;
- ``decoupled_strict`` — AsyncSCSTTrainer in strict mode: the rollout
  ring replays the sync 1-deep pipeline on the full mesh; pinned
  BIT-identical to ``sync`` (params, per-step metrics, and every token
  row the reward scorer sees) in the in-run parity block;
- ``decoupled``        — the genuinely split topology (rl.actor_fraction
  of the mesh decodes, the rest updates, params broadcast actor-ward
  under rl.staleness_bound); tokens legitimately differ (submesh rng
  folds), so its evidence is throughput + the staleness histogram and
  actor/learner occupancy ledgers, not parity.

Writes ``BENCH_RL_ASYNC.json``: per-rung clips/s/chip and seconds/step,
the strict parity block, the decoupled rung's staleness histogram,
dropped/recounted count, and occupancy, and the r05 comparison
(``vs_r05`` — skipped with the standard reason strings off-TPU or off
the flagship operating point).

Measurement hygiene (bench.py convention): every rung starts from the
SAME initial state and epoch rng; a warmup epoch compiles decode/update
before the timed epoch; only the final blocked readback is trusted.

Usage: python bench_rl_async.py [--smoke] [--batch N] [--steps N]
                                [--rollouts K] [--json PATH]
  --smoke   tiny dims, strict-parity gate, no BENCH_RL_ASYNC.json unless
            --json given — the CPU functional gate scripts/lint.sh runs
            (JAX_PLATFORMS=cpu)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# actor/learner submeshes need devices: force 8 fake CPU devices BEFORE
# jax's backend initializes (no-op for the TPU backend)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

# flagship RL operating point (bench.py's constants)
BATCH = 1792
FRAMES = 20
MAX_LEN = 30
K_ROLLOUTS = 5
VOCAB = 9000

# round-5 synchronous loop on TPU v5 lite (round-5 record, removed in PR 21)
R05_RL = {"clips_per_s_per_chip": 3629.42, "device_kind": "TPU v5 lite",
          "batch": 1792, "rollouts": 5}


class _TokenReward:
    """Rigged scorer (+1 per target token) that RECORDS every row batch:
    the parity block pins the token streams, not just the final params."""

    def __init__(self, target: int):
        self.target = target
        self.calls: list = []

    def __call__(self, video_ids, rows):
        rows = np.asarray(rows)
        self.calls.append(rows.copy())
        return (rows == self.target).sum(axis=1).astype(np.float32)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims; the CPU strict-parity gate")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--rollouts", type=int, default=K_ROLLOUTS)
    ap.add_argument("--json", default="", metavar="PATH",
                    help="output path (default BENCH_RL_ASYNC.json; smoke "
                         "writes no file unless given)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import (
        ModelConfig,
        RLConfig,
        TrainConfig,
    )
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl import AsyncSCSTTrainer, SCSTTrainer
    from cst_captioning_tpu.train import (
        create_train_state,
        make_mesh,
        make_optimizer,
        replicate,
        shard_batch,
    )

    if args.smoke:
        batch = args.batch or 8
        steps = args.steps or 4
        vocab_n, frames, max_len = 97, 4, 8
        modal = (("resnet", 16),)
        d_embed = d_hidden = 16
        d_att = 8
        K = 2 if args.rollouts == K_ROLLOUTS else args.rollouts
    else:
        # full dims are decode-bound far past a CPU bench budget; off-TPU
        # the committed ledger rides mid dims + the standard rerun note
        # (the BENCH_COMMS.json convention)
        on_tpu = jax.default_backend() == "tpu"
        batch = args.batch or (BATCH if on_tpu else 64)
        steps = args.steps or 8
        vocab_n = VOCAB if on_tpu else 1000
        frames = FRAMES if on_tpu else 8
        max_len = MAX_LEN if on_tpu else 16
        modal = (("resnet", 2048), ("c3d", 500)) if on_tpu else \
            (("resnet", 128),)
        d_embed = d_hidden = 512 if on_tpu else 64
        d_att = 256 if on_tpu else 32
        K = args.rollouts

    n_chips = len(jax.devices())
    kind = jax.devices()[0].device_kind
    backend = jax.default_backend()
    print(f"bench_rl_async: backend={backend} chips={n_chips} B={batch} "
          f"K={K} T={max_len} steps={steps}", file=sys.stderr)

    mcfg = ModelConfig(
        vocab_size=vocab_n, modalities=modal, d_embed=d_embed,
        d_hidden=d_hidden, d_att=d_att, encoder="temporal_attention",
        dropout=0.0, max_len=max_len, max_frames=frames, dtype="float32",
    )
    model = CaptionModel(mcfg)
    rng = np.random.default_rng(0)
    feats = {
        name: jnp.asarray(rng.normal(size=(batch, frames, dim)), jnp.float32)
        for name, dim in modal
    }
    masks = {k: jnp.ones((batch, frames), jnp.float32) for k in feats}
    labels = jnp.asarray(
        rng.integers(4, vocab_n, size=(batch, max_len)), jnp.int32
    )
    tx = make_optimizer(TrainConfig(lr=1e-4, grad_clip=5.0), 10)
    state0 = create_train_state(model, tx, (feats, masks, labels), seed=1)

    mesh = make_mesh()
    state_r = replicate(mesh, state0)
    f_s, m_s = shard_batch(mesh, (feats, masks))
    vids = [f"v{i}" for i in range(batch)]
    batches = [(f_s, m_s, vids, None)] * steps

    rcfg = RLConfig(
        enabled=True, num_rollouts=K, baseline="greedy", pipelined=True,
        rollout_depth=2, staleness_bound=1,
    )

    def run_epoch(trainer):
        # warmup epoch compiles decode/update/broadcast off the clock
        trainer.train_epoch(state_r, iter(batches[:2]), jax.random.key(1))
        t0 = time.perf_counter()
        s, m = trainer.train_epoch(state_r, iter(batches), jax.random.key(9))
        jax.block_until_ready(s.params)
        return s, m, time.perf_counter() - t0

    results: dict[str, dict] = {}
    finals: dict[str, object] = {}
    rewards: dict[str, list] = {}

    # -- sync baseline --------------------------------------------------------
    r_sync = _TokenReward(7)
    t0 = time.perf_counter()
    sync = SCSTTrainer(model, r_sync, rcfg, mesh=mesh)
    s, m, sec = run_epoch(sync)
    print(f"bench_rl_async: sync compile+epoch "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
    finals["sync"] = jax.tree.map(np.asarray, s.params)
    rewards["sync"] = [c for i, c in enumerate(r_sync.calls) if i >= 4]
    results["sync"] = {
        "seconds_per_step": round(sec / steps, 4),
        "clips_per_s_per_chip": round(batch * steps / sec / n_chips, 2),
    }

    # -- strict replay: the parity rung --------------------------------------
    r_strict = _TokenReward(7)
    strict = AsyncSCSTTrainer(model, r_strict, rcfg, mesh=mesh, strict=True,
                              batch_size=batch)
    s, m, sec = run_epoch(strict)
    finals["decoupled_strict"] = jax.tree.map(np.asarray, s.params)
    rewards["decoupled_strict"] = [
        c for i, c in enumerate(r_strict.calls) if i >= 4
    ]
    results["decoupled_strict"] = {
        "seconds_per_step": round(sec / steps, 4),
        "clips_per_s_per_chip": round(batch * steps / sec / n_chips, 2),
        "staleness_histogram": {
            str(k): v for k, v in sorted(strict.last_staleness.items())
        },
        "dropped_stale": strict.last_dropped,
        "occupancy": {
            k: round(v, 4) for k, v in strict.last_occupancy.items()
        },
    }

    # -- genuinely decoupled ---------------------------------------------------
    r_dec = _TokenReward(7)
    dec = AsyncSCSTTrainer(model, r_dec, rcfg, mesh=mesh, batch_size=batch)
    s, m, sec = run_epoch(dec)
    finals["decoupled"] = jax.tree.map(np.asarray, s.params)
    results["decoupled"] = {
        "seconds_per_step": round(sec / steps, 4),
        "clips_per_s_per_chip": round(batch * steps / sec / n_chips, 2),
        "n_actors": dec._plan.n_actors if dec._plan else 1,
        "n_learners": dec._plan.n_learners if dec._plan else 1,
        "staleness_histogram": {
            str(k): v for k, v in sorted(dec.last_staleness.items())
        },
        "dropped_stale": dec.last_dropped,
        "occupancy": {
            k: round(v, 4) for k, v in dec.last_occupancy.items()
        },
    }

    for name, r in results.items():
        r["speedup_vs_sync"] = round(
            results["sync"]["seconds_per_step"] / r["seconds_per_step"], 3
        )
        print(f"bench_rl_async: {name} {r['seconds_per_step'] * 1e3:.1f}"
              f"ms/step  {r['clips_per_s_per_chip']} clips/s/chip",
              file=sys.stderr)

    # -- strict parity: params AND the scored token streams -------------------
    params_exact = all(
        np.array_equal(x, y) for x, y in zip(
            jax.tree.leaves(finals["sync"]),
            jax.tree.leaves(finals["decoupled_strict"]),
        )
    )
    tokens_exact = (
        len(rewards["sync"]) == len(rewards["decoupled_strict"])
        and all(np.array_equal(a, b) for a, b in zip(
            rewards["sync"], rewards["decoupled_strict"]
        ))
    )
    parity = {
        "strict_params_bit_exact": bool(params_exact),
        "strict_scored_tokens_bit_exact": bool(tokens_exact),
        "strict_nothing_dropped": results["decoupled_strict"][
            "dropped_stale"] == 0,
    }
    ok = all(parity.values())
    if args.smoke and not ok:
        sys.exit(f"bench_rl_async: SMOKE FAILURE — strict replay diverged "
                 f"from the sync schedule: {parity}")

    out = {
        "metric": "rl_clips_per_s_per_chip",
        "batch": batch,
        "rollouts": K,
        "max_len": max_len,
        "steps": steps,
        "device_kind": kind,
        "backend": backend,
        "n_chips": n_chips,
        "smoke": bool(args.smoke),
        "rollout_depth": rcfg.rollout_depth,
        "staleness_bound": rcfg.staleness_bound,
        "actor_fraction": rcfg.actor_fraction,
        "rungs": results,
        "parity": parity,
        "parity_ok": bool(ok),
        "note": (
            None if backend == "tpu" else
            "non-TPU run at mid dims: the strict parity block, staleness "
            "histogram, and occupancy ledgers are platform-independent "
            "(the acceptance content); clips/s/chip measures CPU compute "
            "where the fused decode dominates regardless of topology, so "
            "the decoupled overlap win does NOT show. Regenerate on TPU "
            "at flagship dims for throughput acceptance (vs_r05)."
        ),
        "r05_reference": R05_RL,
        "vs_r05": (
            {
                name: round(
                    r["clips_per_s_per_chip"]
                    / R05_RL["clips_per_s_per_chip"], 3
                )
                for name, r in results.items()
            }
            if backend == "tpu" and batch == BATCH and max_len == MAX_LEN
            else "skipped_non_tpu" if backend != "tpu"
            else "skipped_non_flagship_dims"
        ),
    }
    print(json.dumps(out))
    path = args.json or ("" if args.smoke else "BENCH_RL_ASYNC.json")
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"bench_rl_async: wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
