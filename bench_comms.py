"""Gradient-communication bench: the RL update's allreduce ladder.

Round-5 put the RL update at bw_util 0.451 / MFU 0.199 (round-5 record,
removed in PR 21; code older than PRs 1–20) — bandwidth-bound, and its allreduce was spelled one psum per parameter
leaf. This bench isolates that update program and measures the
parallel/comms.py ladder against it on a data mesh over every visible
device:

- ``per_leaf_f32``   — the pre-PR spelling (``comm=None``): one f32 psum
  per leaf; the bit-exactness baseline;
- ``bucketed_f32``   — family-ordered size-targeted buckets
  (``CommConfig()``, train.comm_bucket_mb): same bytes, far fewer
  messages; pinned BIT-identical to per_leaf_f32 in the in-run parity
  block (psum is elementwise);
- ``bucketed_bf16``  — grads ride the wire in bfloat16
  (``comm_dtype="bf16"``), halving bytes-on-wire; params/Adam moments
  stay f32 (master accumulation); tolerance-graded parity;
- ``overlapped``     — the chunked update (``rl.update_chunks=2``) with
  the "defer" double-buffered per-chunk reduction, so each chunk's psum
  can hide behind the next chunk's backward; pinned BIT-identical to the
  "eager" per-chunk-reduce reference in-run (identical float order), and
  ledgered honestly at (chunks+1)x the payload bytes.

Writes ``BENCH_COMMS.json``: per-rung analytic bytes-on-wire, message/
bucket counts (parallel/comms.ledger), update seconds/step, compile-time
FLOPs when XLA exposes them (obs/flops.compiled_cost — the same number
the trainer's flops.rl.update counter now prefers, so ``cli.obs_report``
and this ledger agree), and the parity block. Each rung's timed dispatch
runs under PR 6's ``collective_span`` so DCN/ICI stalls surface exactly
as they do in training.

Measurement hygiene (bench.py convention): every rep uploads a PERTURBED
advantage under a fresh fold and the returned state threads forward, so
repeated identical dispatches can't be memoized; only the final readback
of the chained loss is trusted.

Usage: python bench_comms.py [--smoke] [--batch N] [--steps N]
                             [--rollouts K] [--json PATH]
  --smoke   tiny dims, 2 steps, parity + bytes-accounting gate, no
            BENCH_COMMS.json unless --json given — the CPU functional
            gate scripts/lint.sh runs (JAX_PLATFORMS=cpu)
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

# a data mesh needs devices: force 8 fake CPU devices BEFORE jax's backend
# initializes (no-op for the TPU backend — the flag only shapes the host
# CPU platform)
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import numpy as np

# flagship RL update operating point (bench.py's constants)
BATCH = 1792
FRAMES = 20
MAX_LEN = 30
K_ROLLOUTS = 4  # divisible by the overlapped rung's 2 chunks
VOCAB = 9000

# round-5 update baseline on TPU v5 lite (round-5 record, removed in PR 21:
# programs.update)
R05_UPDATE = {"seconds_per_step": 0.7, "mfu": 0.199, "bw_util": 0.451,
              "device_kind": "TPU v5 lite", "batch": 1792}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims / 2 steps; the CPU functional gate")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--rollouts", type=int, default=K_ROLLOUTS)
    ap.add_argument("--json", default="", metavar="PATH",
                    help="output path (default BENCH_COMMS.json; smoke "
                         "writes no file unless given)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from cst_captioning_tpu.config.config import ModelConfig, TrainConfig
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.obs.flops import compiled_cost, peak_flops
    from cst_captioning_tpu.parallel.comms import CommConfig, ledger
    from cst_captioning_tpu.resilience.health import collective_span
    from cst_captioning_tpu.rl import make_parallel_rl_update
    from cst_captioning_tpu.train import (
        create_train_state,
        make_mesh,
        make_optimizer,
        replicate,
        shard_batch,
    )

    if args.smoke:
        batch = args.batch or 8
        steps = args.steps or 2
        vocab_n, frames, max_len = 97, 4, 8
        modal = (("resnet", 16),)
        d_embed = d_hidden = 16
        d_att = 8
    else:
        batch = args.batch or BATCH
        steps = args.steps or 8
        vocab_n, frames, max_len = VOCAB, FRAMES, MAX_LEN
        modal = (("resnet", 2048), ("c3d", 500))
        d_embed = d_hidden = 512
        d_att = 256
    K = args.rollouts
    chunks = 2
    if K % chunks:
        sys.exit(f"bench_comms: --rollouts {K} must be divisible by "
                 f"{chunks} (the overlapped rung's chunk count)")

    n_chips = len(jax.devices())
    kind = jax.devices()[0].device_kind
    backend = jax.default_backend()
    print(f"bench_comms: backend={backend} chips={n_chips} B={batch} "
          f"K={K} T={max_len}", file=sys.stderr)

    # f32 params regardless of the full-run activation dtype: the bench
    # measures the reduction of f32 master grads (the production layout)
    cfg = ModelConfig(
        vocab_size=vocab_n, modalities=modal, d_embed=d_embed,
        d_hidden=d_hidden, d_att=d_att, encoder="temporal_attention",
        dropout=0.0, max_len=max_len, max_frames=frames, dtype="float32",
    )
    model = CaptionModel(cfg)
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
    kb = NamedSharding(mesh, P(None, "data"))
    samples = jax.device_put(jnp.asarray(
        rng.integers(2, vocab_n, size=(K, batch, max_len)), jnp.int32
    ), kb)
    adv0 = jnp.asarray(rng.normal(size=(K, batch)), jnp.float32)
    valid = shard_batch(mesh, jnp.ones((batch,), jnp.float32))
    f_s, m_s = shard_batch(mesh, (feats, masks))
    state_r = replicate(mesh, state0)

    # (name, comm, chunks); the eager rung is the overlapped rung's
    # bit-exactness reference, bench-internal — it is measured but the
    # acceptance ladder is the four ISSUE rungs
    rungs = (
        ("per_leaf_f32", None, 1),
        ("bucketed_f32", CommConfig(), 1),
        ("bucketed_bf16", CommConfig(dtype="bf16"), 1),
        ("overlapped", CommConfig(overlap="defer"), chunks),
        ("overlapped_eager_ref", CommConfig(overlap="eager"), chunks),
    )

    try:
        peak = peak_flops(kind)
    except KeyError:
        peak = None  # no published peak (the CPU smoke): MFU not measured
    results: dict[str, dict] = {}
    updated: dict[str, object] = {}
    for name, comm, n_chunks in rungs:
        update = make_parallel_rl_update(
            model, mesh, chunks=n_chunks, comm=comm
        )

        t0 = time.perf_counter()
        # parity material first: every rung updates the SAME state with the
        # SAME batch (donate off, so state_r is reusable across rungs)
        s1, m1 = update(state_r, f_s, m_s, samples, jax.device_put(adv0, kb),
                        valid)
        updated[name] = jax.tree.map(np.asarray, (s1.params, m1["rl_loss"]))
        print(f"bench_comms: {name} compile+first step "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)

        cost = compiled_cost(
            update, state_r, f_s, m_s, samples, jax.device_put(adv0, kb),
            valid,
        )

        t0 = time.perf_counter()
        st, acc = s1, jnp.float32(0)
        for i in range(steps):
            adv = jax.device_put(adv0 + np.float32(1e-3) * (i + 1), kb)
            with collective_span(f"bench_comms.{name}"):
                st, m = update(st, f_s, m_s, samples, adv, valid)
            acc = acc + m["rl_loss"]
        float(np.asarray(acc))  # one readback forcing the whole chain
        sec = (time.perf_counter() - t0) / steps

        # analytic wire accounting: the unoverlapped update reduces the
        # params-shaped grad tree once; the overlapped one reduces it per
        # chunk plus the final encoder-cotangent fold -> chunks + 1
        led = ledger(
            state0.params, comm,
            reductions=(n_chunks + 1) if (comm is not None and
                                          comm.overlap != "off") else 1,
        )
        results[name] = {
            "seconds_per_step": round(sec, 4),
            "chunks": n_chunks,
            "buckets": led["buckets"],
            "messages_per_update": led["messages_per_update"],
            "bytes_on_wire_per_update": led["bytes_on_wire_per_update"],
            "compiled_flops": cost["flops"] if cost else None,
            "mfu": (
                round(cost["flops"] / sec / peak / max(n_chips, 1), 4)
                if cost and peak else None
            ),
        }
        print(f"bench_comms: {name} {sec * 1e3:.1f}ms/step "
              f"bytes={led['bytes_on_wire_per_update']} "
              f"messages={led['messages_per_update']}", file=sys.stderr)

    base = results["per_leaf_f32"]
    for r in results.values():
        r["speedup_vs_per_leaf"] = round(
            base["seconds_per_step"] / r["seconds_per_step"], 3
        )
        r["wire_bytes_ratio_vs_per_leaf"] = round(
            base["bytes_on_wire_per_update"] / r["bytes_on_wire_per_update"],
            3,
        )

    def _bitexact(a, b):
        pa, la = updated[a]
        pb, lb = updated[b]
        return bool(
            np.array_equal(la, lb)
            and all(np.array_equal(x, y) for x, y in zip(
                jax.tree.leaves(pa), jax.tree.leaves(pb)))
        )

    def _max_abs_diff(a, b):
        pa, _ = updated[a]
        pb, _ = updated[b]
        return float(max(
            np.max(np.abs(x - y))
            for x, y in zip(jax.tree.leaves(pa), jax.tree.leaves(pb))
        ))

    bf16_diff = _max_abs_diff("bucketed_bf16", "per_leaf_f32")
    # one Adam step from identical state: bf16 wire noise perturbs the
    # update by O(2^-8 * lr) — pin an order of magnitude above that
    bf16_tol = 5e-3
    parity = {
        "bucketed_f32_bit_exact": _bitexact("bucketed_f32", "per_leaf_f32"),
        "overlapped_defer_eq_eager_bit_exact": _bitexact(
            "overlapped", "overlapped_eager_ref"
        ),
        "bucketed_bf16_max_abs_param_diff": bf16_diff,
        "bucketed_bf16_tolerance": bf16_tol,
        "bucketed_bf16_within_tolerance": bool(bf16_diff <= bf16_tol),
    }
    bytes_ratio = (
        base["bytes_on_wire_per_update"]
        / results["bucketed_bf16"]["bytes_on_wire_per_update"]
    )
    parity["bf16_wire_bytes_ratio"] = round(bytes_ratio, 3)

    ok = (
        parity["bucketed_f32_bit_exact"]
        and parity["overlapped_defer_eq_eager_bit_exact"]
        and parity["bucketed_bf16_within_tolerance"]
        and bytes_ratio >= 1.8
        and results["bucketed_f32"]["messages_per_update"]
        < results["per_leaf_f32"]["messages_per_update"]
    )
    if args.smoke and not ok:
        sys.exit(f"bench_comms: SMOKE FAILURE — comms parity/accounting "
                 f"gate failed: {parity}")

    out = {
        "metric": "rl_update_seconds_per_step",
        "batch": batch,
        "rollouts": K,
        "max_len": max_len,
        "steps": steps,
        "device_kind": kind,
        "backend": backend,
        "n_chips": n_chips,
        "smoke": bool(args.smoke),
        "comm_bucket_mb": CommConfig().bucket_mb,
        "assumed_peak_bf16_flops": peak,
        "rungs": results,
        "parity": parity,
        "parity_ok": bool(ok),
        "note": (
            None if backend == "tpu" else
            "non-TPU run: bytes-on-wire, bucket/message counts, and the "
            "parity block are platform-independent (the acceptance "
            "content); seconds/step measures CPU compute where the psum "
            "is a local copy, so wire-cost wins and the overlap's latency "
            "hiding do NOT show. Regenerate on TPU for timing acceptance "
            "(vs_r05_update)."
        ),
        "r05_update_reference": R05_UPDATE,
        "vs_r05_update": (
            {
                name: round(
                    R05_UPDATE["seconds_per_step"] / r["seconds_per_step"], 3
                )
                for name, r in results.items()
            }
            if backend == "tpu" and batch == BATCH and max_len == MAX_LEN
            else "skipped_non_tpu" if backend != "tpu"
            else "skipped_non_flagship_dims"
        ),
    }
    print(json.dumps(out))
    path = args.json or ("" if args.smoke else "BENCH_COMMS.json")
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"bench_comms: wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
