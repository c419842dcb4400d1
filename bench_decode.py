"""RL decode-program bench: two-loop vs fused one-loop vs Pallas kernels.

Round-5 put the RL decode program at 85.1% of sequential step time — 2.676
s/step at MFU 0.010 / bw_util 0.015 on a v5e (round-5 record, removed in PR
21; measured on code older than PRs 1–20) — the single
biggest lever on the north-star ``rl_clips_per_sec_per_chip``. This bench
isolates exactly that program and measures the fast-path ladder against it:

- ``two_loop_xla``      — the round-5 baseline: ``greedy_decode`` then
  ``sample_decode`` as two sequential scan loops in one jitted program
  (``make_rl_decode(fused=False)``);
- ``fused_xla``         — the one-loop stride-1 uncompacted baseline:
  greedy rides as lane 0 of the (1+K)-lane rollout scan
  (decoding/fused.py) — every other row is pinned token-exact against it;
- ``fused_xla_s{S}``    — the stride sweep (S in {4, 8, 16}): the driving
  while loop advances S steps per iteration with finished-lane compaction
  between strides; ``fused_xla_s8_nocompact`` is the compaction-off row;
- ``fused_pallas``      — the stride-1 loop stepping the per-step
  weight-stationary kernel (``model.decode_impl="pallas"``);
- ``fused_pallas_s{S}`` — ONE multi-step stride-kernel launch per S steps,
  token selection and next-token embedding lookup in-kernel, decoder
  weights VMEM-resident across the whole stride (ops/decode_pallas.py).

Writes ``BENCH_DECODE.json``: per-impl seconds/step, analytic FLOPs/bytes
(compaction-aware via the measured lane-step ledger), roofline MFU /
bw_util, speedup vs the in-run two-loop baseline, a per-impl ``compaction``
block (lane-steps computed vs skipped — the tokens-stepped-saved ledger,
``rl.scst.compaction_stats``), and the round-5 reference constants. The
``vs_r05_two_loop`` acceptance field is a dict of speedups on a flagship
TPU run and a machine-checkable skip reason (``"skipped_non_tpu"`` /
``"skipped_non_flagship_dims"``) everywhere else. A parity block records
(a) every stride/compaction row decoded bit-identical tokens to the
stride-1 fused loop, and (b) the Pallas rows' token match fraction vs the
two-loop reference in f32 AND bf16 — the in-kernel selection's tie-break
parity (near-tie argmax flips from f32-vs-bf16 accumulation-order logit
noise are the ONLY expected source of mismatch; tests pin that cause).

Measurement hygiene (see bench.py's eval bench): every rep decodes
PERTURBED features with a fresh fold of the rng and feeds a token checksum
forward, so the timed region ends in one host readback that depends on
every rep (nothing can be elided or still be in flight when the clock stops).

Usage: python bench_decode.py [--smoke] [--batch N] [--steps N]
                              [--rollouts K] [--json PATH]
  --smoke   tiny dims, 2 steps, no BENCH_DECODE.json unless --json given —
            the CPU functional gate scripts/lint.sh runs (JAX_PLATFORMS=cpu)
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from cst_captioning_tpu.obs.flops import (
    decode_flops_per_clip,
    enc_and_per_tok_flops,
    peak_flops,
    peak_hbm,
    stride_steps,
)

# flagship RL operating point (bench.py's constants; decode-only program)
BATCH = 1792
FRAMES = 20
MAX_LEN = 30
K_ROLLOUTS = 5
VOCAB = 9000

# round-5 decode baseline on TPU v5 lite at the dims above (round-5 record,
# removed in PR 21: programs.decode, on code older than PRs 1–20) — the
# acceptance reference the JSON compares against
R05_TWO_LOOP = {"seconds_per_step": 2.676, "mfu": 0.010, "bw_util": 0.015,
                "device_kind": "TPU v5 lite", "batch": 1792}

# (name, decode_impl, stride, compact, fused); fused_xla is the stride-1
# uncompacted exactness baseline every other fused row is gated against
FULL_IMPLS = (
    ("two_loop_xla", "xla", 1, False, False),
    ("fused_xla", "xla", 1, False, True),
    ("fused_xla_s4", "xla", 4, True, True),
    ("fused_xla_s8", "xla", 8, True, True),
    ("fused_xla_s16", "xla", 16, True, True),
    ("fused_xla_s8_nocompact", "xla", 8, False, True),
    ("fused_pallas", "pallas", 1, False, True),
    ("fused_pallas_s8", "pallas", 8, True, True),
)
# the smoke budget (interpret-mode Pallas on CPU) keeps one row per
# mechanism: stride+compaction XLA, per-step kernel, stride kernel
SMOKE_IMPLS = (
    ("two_loop_xla", "xla", 1, False, False),
    ("fused_xla", "xla", 1, False, True),
    ("fused_xla_s4", "xla", 4, True, True),
    ("fused_pallas", "pallas", 1, False, True),
    ("fused_pallas_s4", "pallas", 4, True, True),
)


def _decode_bytes(B, K, T, F, d_embed, d_hidden, d_att, V, feat_dims,
                  fused: bool, act_bytes: int, stride: int = 1) -> float:
    """Analytic HBM traffic of the decode program (bench.py's roofline
    conventions: weights + memory bank re-read per step, rollout broadcasts
    of the memory counted once — a lower bound; per-step [rows, V] f32
    logits counted as one write + one read; features read once in f32).
    The stride kernel replaces the logits round-trip with the Gumbel-noise
    stream (same [rows, V] f32 order of magnitude), so the model is left
    unchanged — it stays a lower bound for every impl."""
    M = len(feat_dims) * F
    E, H, A = d_embed, d_hidden, d_att
    enc_bytes = (
        B * F * sum(feat_dims) * 4
        + B * M * (E + A) * act_bytes
        + 4 * (sum(feat_dims) * E + E * A)
    )
    w_step = 4 * (H * A + (2 * E) * (4 * H) + H * (4 * H) + H * V)
    mem_step = B * M * (E + A) * act_bytes
    lanes = 1 + K

    def step_bytes(rows):
        return w_step + mem_step + 2 * rows * V * 4

    T_eff = stride_steps(T, stride)
    if fused:
        return float(enc_bytes + T_eff * step_bytes(lanes * B))
    return float(2 * enc_bytes + T * (step_bytes(B) + step_bytes(K * B)))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims / 2 steps; the CPU functional gate")
    ap.add_argument("--batch", type=int, default=None)
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--rollouts", type=int, default=K_ROLLOUTS)
    ap.add_argument("--json", default="", metavar="PATH",
                    help="output path (default BENCH_DECODE.json; smoke "
                         "writes no file unless given)")
    args = ap.parse_args()

    import dataclasses

    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import ModelConfig
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl import make_rl_decode
    from cst_captioning_tpu.rl.scst import compaction_stats

    if args.smoke:
        batch = args.batch or 8
        steps = args.steps or 2
        vocab_n, frames, max_len = 97, 6, 12
        modal = (("resnet", 16),)
        d_embed = d_hidden = 16
        d_att = 8
        dtype = "float32"
    else:
        batch = args.batch or BATCH
        steps = args.steps or 8
        vocab_n, frames, max_len = VOCAB, FRAMES, MAX_LEN
        modal = (("resnet", 2048), ("c3d", 500))
        d_embed = d_hidden = 512
        d_att = 256
        dtype = "bfloat16"
    K = args.rollouts

    base = ModelConfig(
        vocab_size=vocab_n, modalities=modal, d_embed=d_embed,
        d_hidden=d_hidden, d_att=d_att, encoder="temporal_attention",
        dropout=0.5, max_len=max_len, max_frames=frames, dtype=dtype,
    )
    impls = SMOKE_IMPLS if args.smoke else FULL_IMPLS
    models = {
        name: (
            CaptionModel(dataclasses.replace(
                base, decode_impl=impl, decode_stride=stride,
                decode_compact=compact,
            )),
            fused, stride, compact,
        )
        for name, impl, stride, compact, fused in impls
    }

    n_chips = len(jax.devices())
    kind = jax.devices()[0].device_kind
    backend = jax.default_backend()
    try:
        peak, hbm = peak_flops(kind), peak_hbm(kind)
    except KeyError:
        # no published peak for this device (the CPU smoke): utilization
        # is not measured, and says so as null
        peak = hbm = None
    print(f"bench_decode: backend={backend} chips={n_chips} B={batch} "
          f"K={K} T={max_len} dtype={dtype}", file=sys.stderr)

    rng = np.random.default_rng(0)
    feats = {
        name: jnp.asarray(rng.normal(size=(batch, frames, dim)), jnp.float32)
        for name, dim in modal
    }
    masks = {k: jnp.ones((batch, frames), jnp.float32) for k in feats}
    labels = jnp.asarray(
        rng.integers(4, vocab_n, size=(batch, max_len)), jnp.int32
    )
    params = models["fused_xla"][0].init(jax.random.key(0), feats, masks, labels)
    # nudge the EOS logit so sampled lanes finish at varied lengths, like a
    # trained policy (round 5's depth histogram is WHY compaction exists):
    # with raw random init nothing ever emits EOS, the early-exit loop
    # always runs the full budget, and the compaction ledger reads zero —
    # a regime no converged SCST policy is in. Every impl shares these
    # params, so the bit-exactness parity gates are unaffected.
    bias = params["params"]["cell"]["out_proj"]["bias"]
    from cst_captioning_tpu.config.config import EOS_ID
    params["params"]["cell"]["out_proj"]["bias"] = bias.at[EOS_ID].add(2.0)
    key = jax.random.key(42)

    feat_dims = tuple(d for _, d in modal)
    act_bytes = 2 if dtype == "bfloat16" else 4
    results: dict[str, dict] = {}
    decoded: dict[str, tuple] = {}
    for name, (model, fused, stride, compact) in models.items():
        decode = make_rl_decode(model, K, max_len=max_len, fused=fused)

        @jax.jit
        def step(p, f, m, i, acc, decode=decode):
            f = {k: v + (i.astype(v.dtype) * 1e-6) for k, v in f.items()}
            g, s = decode(p, f, m, jax.random.fold_in(key, i))
            return (
                acc + jnp.sum(g.astype(jnp.float32))
                + jnp.sum(s.astype(jnp.float32))
            )

        t0 = time.perf_counter()
        acc = step(params, feats, masks, jnp.int32(0), jnp.float32(0))
        float(np.asarray(acc))
        print(f"bench_decode: {name} compile+first step "
              f"{time.perf_counter() - t0:.1f}s", file=sys.stderr)
        # parity material: the unperturbed program output under the run key
        decoded[name] = jax.tree.map(
            np.asarray, decode(params, feats, masks, key)
        )

        t0 = time.perf_counter()
        acc = jnp.float32(0)
        for i in range(steps):
            acc = step(params, feats, masks, jnp.int32(i + 1), acc)
        float(np.asarray(acc))  # one readback forcing the whole chain
        sec = (time.perf_counter() - t0) / steps

        g_np, s_np = decoded[name]
        comp = compaction_stats(
            g_np, s_np, stride if (fused and (stride > 1 or compact)) else 1,
            max_len, compact=compact,
        )
        lane_total = comp["lanes_stepped"] + comp["lanes_skipped"]
        active_frac = (
            comp["lanes_stepped"] / lane_total if lane_total else 1.0
        )
        flops = batch * decode_flops_per_clip(
            K=K, T=max_len, F=frames, d_embed=d_embed, d_hidden=d_hidden,
            d_att=d_att, V=vocab_n, feat_dims=feat_dims, fused=fused,
            stride=stride if fused else 1, active_frac=active_frac,
        )
        nbytes = _decode_bytes(
            batch, K, max_len, frames, d_embed, d_hidden, d_att, vocab_n,
            feat_dims, fused, act_bytes, stride=stride if fused else 1,
        )
        results[name] = {
            "seconds_per_step": round(sec, 4),
            "decode_stride": stride,
            "compact": compact,
            # scan steps the program dispatches per RL batch (the latency
            # axis the fusion halves and the stride kernel batches): two
            # loops of T vs one loop of the stride-padded budget
            "loop_steps_budget": (
                2 * max_len if not fused else stride_steps(max_len, stride)
            ),
            # driving-loop iterations = pallas_call launches on the stride
            # kernel path (ONE per stride instead of one per step)
            "loop_iters_budget": (
                2 * max_len if not fused
                else -(-max_len // max(stride, 1))
            ),
            # the tokens-stepped-saved ledger measured from THIS run's
            # decoded tokens (rl.scst.compaction_stats — same math as the
            # rl.decode.compaction counters in the run report)
            "compaction": {
                "lanes_stepped": comp["lanes_stepped"],
                "lanes_skipped": comp["lanes_skipped"],
                "saved_frac": round(
                    comp["lanes_skipped"] / lane_total, 4
                ) if lane_total else 0.0,
            },
            "flops": round(flops),
            "bytes": round(nbytes),
            "mfu": (round(flops / sec / peak / max(n_chips, 1), 4)
                    if peak else None),
            "bw_util": (round(nbytes / sec / hbm / max(n_chips, 1), 4)
                        if hbm else None),
        }
        print(f"bench_decode: {name} {sec * 1e3:.1f}ms/step "
              f"mfu={results[name]['mfu']:.4f} "
              f"bw_util={results[name]['bw_util']:.4f} "
              f"compaction_saved={results[name]['compaction']['saved_frac']}",
              file=sys.stderr)

    base_sec = results["two_loop_xla"]["seconds_per_step"]
    for name, r in results.items():
        r["speedup_vs_two_loop"] = round(base_sec / r["seconds_per_step"], 3)

    g0, s0 = decoded["two_loop_xla"]
    gf, sf = decoded["fused_xla"]
    parity = {
        "fused_xla_greedy_bit_exact": bool(np.array_equal(gf, g0)),
        "fused_xla_samples_bit_exact": bool(np.array_equal(sf, s0)),
    }
    # every stride/compaction XLA row must be BIT-exact vs the stride-1
    # uncompacted fused loop (the acceptance contract, also pinned by
    # tests/test_decoding.py)
    stride_exact = True
    for name, (model, fused, stride, compact) in models.items():
        if not name.startswith("fused_xla_s"):
            continue
        gn, sn = decoded[name]
        ok = np.array_equal(gn, gf) and np.array_equal(sn, sf)
        parity[f"{name}_bit_exact"] = bool(ok)
        stride_exact = stride_exact and ok
    # the Pallas rows select tokens from kernel-computed logits whose
    # accumulation order differs from XLA's — near-tie argmax flips are
    # expected and pinned as the ONLY mismatch cause by
    # tests/test_ops_decode_pallas.py; report the match fraction
    for name in decoded:
        if name.startswith("fused_pallas"):
            parity[f"{name}_token_match_frac"] = round(float(
                np.mean(decoded[name][1] == s0)
            ), 4)
    if args.smoke:
        # bf16 in-kernel selection parity at the same tiny dims: the stride
        # kernel computes f32 from bf16 params/activations, so token match
        # is tolerance-grade, not bit-grade — gate it loosely
        m_bf = CaptionModel(dataclasses.replace(
            base, dtype="bfloat16", decode_impl="pallas", decode_stride=4,
            decode_compact=True,
        ))
        m_bf_ref = CaptionModel(dataclasses.replace(base, dtype="bfloat16"))
        d_bf = make_rl_decode(m_bf, K, max_len=max_len)(
            params, feats, masks, key
        )
        d_bf_ref = make_rl_decode(m_bf_ref, K, max_len=max_len)(
            params, feats, masks, key
        )
        parity["in_kernel_selection_bf16_token_match_frac"] = round(float(
            np.mean(np.asarray(d_bf[1]) == np.asarray(d_bf_ref[1]))
        ), 4)

    if args.smoke:
        ok = (
            parity["fused_xla_greedy_bit_exact"]
            and parity["fused_xla_samples_bit_exact"]
            and stride_exact
            and parity.get("fused_pallas_s4_token_match_frac", 0.0) >= 0.9
            and parity.get(
                "in_kernel_selection_bf16_token_match_frac", 0.0
            ) >= 0.8
        )
        if not ok:
            sys.exit("bench_decode: SMOKE FAILURE — decode parity gate "
                     f"failed: {parity}")

    flagship = (not args.smoke and batch == BATCH and K == K_ROLLOUTS
                and max_len == MAX_LEN)
    out = {
        "metric": "rl_decode_seconds_per_step",
        "batch": batch,
        "rollouts": K,
        "max_len": max_len,
        "steps": steps,
        "dtype": dtype,
        "device_kind": kind,
        "backend": backend,
        "smoke": bool(args.smoke),
        "assumed_peak_bf16_flops": peak,
        "assumed_peak_hbm_bytes_per_sec": hbm,
        "impls": results,
        "parity": parity,
        # the acceptance gate: fused/pallas decode vs the ROUND-5 two-loop
        # baseline (only meaningful on TPU at the flagship operating point)
        "note": (
            None if backend == "tpu" else
            "non-TPU run: these numbers measure raw compute only. The "
            "two-loop cost this path removes is per-step dispatch/loop "
            "latency on TPU (round-5 decode ran at MFU 0.010 — "
            "latency-bound, so wall time tracks loop_iters_budget, which "
            "the fused program halves and the stride kernel divides by S); "
            "on CPU the loops are compute-bound and the saved dispatches "
            "do not show (interpret-mode Pallas is additionally pure "
            "overhead). Regenerate on TPU for the acceptance comparison "
            "(vs_r05_two_loop)."
        ),
        "r05_two_loop_reference": R05_TWO_LOOP,
        "vs_r05_two_loop": (
            {
                name: round(
                    R05_TWO_LOOP["seconds_per_step"] / r["seconds_per_step"],
                    3,
                )
                for name, r in results.items()
            }
            if flagship and backend == "tpu"
            else "skipped_non_tpu" if backend != "tpu"
            else "skipped_non_flagship_dims"
        ),
    }
    print(json.dumps(out))
    path = args.json or ("" if args.smoke else "BENCH_DECODE.json")
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"bench_decode: wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
