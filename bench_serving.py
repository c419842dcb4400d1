"""Serving bench: continuous batching vs static batching under an SLO.

Drives :class:`serving.engine.CaptionService` (the always-on continuous-
batching caption service) and the static-batching reference policy over the
SAME seeded traffic traces (serving/traffic.py: Poisson + bursty) on the
SAME hardware, and ledgers the difference in user-visible terms:

- **p50 / p99 request latency** (arrival -> caption, queue wait included);
- **goodput under an SLO**: completed-within-SLO requests per second of
  makespan. The SLO is ``--slo-factor`` x the measured SOLO latency (one
  request through an idle service — the floor any policy could offer), so
  it travels across machines without hand-tuned constants;
- the **continuous-vs-static ratio** — the acceptance field: slotting
  requests into lanes freed between strides must beat waiting to form full
  batches (where early arrivals pay formation wait and everyone pays the
  slowest member's decode).

Arrival rates are CALIBRATED to the machine: the trace's mean rate is
``--load`` x the service's nominal capacity (``capacity / solo_latency``),
so the bench exercises a loaded-but-stable system everywhere instead of a
trivially idle (or hopelessly overloaded) one on slow hosts.

A ``paged_inkernel`` rung re-serves both traces through the pallas stride
kernel's paged path (in-kernel page-table reads from the pool — no dense
[B, W, E] bank per stride) against the same kernel on the dense-gather
reference (``paged=False``), with an in-run token- AND logprob-bit-exact
parity gate between the two, the per-stride bank bytes each path moves
(obs/flops.serving_bank_bytes_per_stride: the gather pays 3x), and a
stress config whose page pool exceeds one batch's dense-bank footprint —
a pool the gather path refuses at construction, which the paged engine
fills via encode-ahead staging.

A parity block re-decodes sampled requests OFFLINE through
``decoding.fused.fused_decode`` and requires token- AND logprob-bit-exact
agreement with the served results (the continuous engine's per-request
determinism contract, also pinned by tests/test_serving.py). It also
covers the ADMISSION seam: grouped (batched) admission encode must be
bit-exact vs per-request admission at f32, and at bf16 the engine's
fall-back to per-request encode is verified engaged, with the
batched-vs-solo bf16 encoder drift it avoids measured and bounded
(tolerance documented in the block). FLOPs for the
MFU field come from XLA's HLO cost analysis of the compiled stride program
(``obs/flops.compiled_cost``) with the analytic model as fallback.

Writes ``BENCH_SERVING.json``. Like BENCH_DECODE.json, a non-TPU run
carries a ``note``: on CPU the stride dispatch overhead is proportionally
larger and absolute latencies are not representative — regenerate on TPU
for the flagship numbers; the policy COMPARISON (same-hardware, same-trace)
is meaningful everywhere.

Usage: python bench_serving.py [--smoke] [--requests N] [--capacity N]
                               [--rollouts K] [--load F] [--slo-factor F]
                               [--json PATH]
  --smoke   tiny dims, asserts goodput > 0 + the parity block — the CPU
            functional gate scripts/lint.sh runs (JAX_PLATFORMS=cpu)
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import time

import numpy as np

from cst_captioning_tpu.obs.flops import (
    enc_and_per_tok_flops,
    peak_flops,
    serving_bank_bytes_per_stride,
)

# flagship serving operating point (bench_decode.py's model dims; serving
# runs far smaller batches than offline RL — lanes are REQUESTS here)
CAPACITY = 8
FRAMES = 20
MAX_LEN = 30
K_ROLLOUTS = 2
VOCAB = 9000


def _percentile(vals: list[float], q: float) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q)) if vals \
        else 0.0


def _policy_stats(report, trace, slo_s: float) -> dict:
    lats = [r.latency_s for r in report.results.values()]
    within = sum(1 for v in lats if v <= slo_s)
    makespan = max(report.wall_s, 1e-9)
    return {
        "completed": report.completed,
        "p50_s": round(_percentile(lats, 50), 4),
        "p99_s": round(_percentile(lats, 99), 4),
        "max_s": round(max(lats), 4) if lats else 0.0,
        "within_slo": within,
        "makespan_s": round(makespan, 4),
        "goodput_rps": round(within / makespan, 4),
        "strides": report.strides,
    }


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny dims; the CPU functional gate")
    ap.add_argument("--requests", type=int, default=None,
                    help="requests per trace")
    ap.add_argument("--capacity", type=int, default=None)
    ap.add_argument("--rollouts", type=int, default=K_ROLLOUTS)
    ap.add_argument("--load", type=float, default=0.7,
                    help="offered load as a fraction of nominal capacity "
                         "(capacity / solo latency) — the loaded-but-"
                         "stable regime where a latency SLO is meaningful")
    ap.add_argument("--slo-factor", type=float, default=None,
                    help="SLO = factor x measured solo latency (default "
                         "1.5; the smoke gate uses 4.0 — at its toy dims "
                         "per-stride dispatch overhead is a large multiple "
                         "of solo, and the smoke asserts function, not "
                         "performance)")
    ap.add_argument("--json", default="", metavar="PATH",
                    help="output path (default BENCH_SERVING.json; smoke "
                         "writes no file unless given)")
    args = ap.parse_args()
    if args.slo_factor is None:
        args.slo_factor = 4.0 if args.smoke else 1.5

    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import EOS_ID, ModelConfig
    from cst_captioning_tpu.decoding.fused import fused_decode
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.serving.engine import (
        CaptionService,
        ClipRequest,
        static_batch_serve,
    )
    from cst_captioning_tpu.serving.traffic import (
        TrafficSpec,
        make_trace,
        synth_request_features,
    )

    if args.smoke:
        capacity = args.capacity or 4
        n_req = args.requests or 10
        vocab_n, frames, max_len = 97, 6, 12
        modal = (("resnet", 16),)
        d_embed = d_hidden = 16
        d_att = 8
        dtype = "float32"
        stride = 4
    else:
        capacity = args.capacity or CAPACITY
        n_req = args.requests or 24
        vocab_n, frames, max_len = VOCAB, FRAMES, MAX_LEN
        modal = (("resnet", 2048), ("c3d", 500))
        d_embed = d_hidden = 512
        d_att = 256
        dtype = "bfloat16"
        stride = 8
    K = args.rollouts

    cfg = ModelConfig(
        vocab_size=vocab_n, modalities=modal, d_embed=d_embed,
        d_hidden=d_hidden, d_att=d_att, encoder="temporal_attention",
        dropout=0.5, max_len=max_len, max_frames=frames, dtype=dtype,
        decode_stride=stride,
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats0 = {
        name: jnp.asarray(rng.normal(size=(1, frames, dim)), jnp.float32)
        for name, dim in modal
    }
    masks0 = {k: jnp.ones((1, frames), jnp.float32) for k in feats0}
    params = model.init(
        jax.random.key(0), feats0, masks0, jnp.zeros((1, max_len), jnp.int32)
    )
    # EOS-biased logits like bench_decode.py: a trained policy emits varied
    # caption lengths, which is the regime continuous batching exploits
    # (lanes free at different strides); raw random init never finishes
    bias = params["params"]["cell"]["out_proj"]["bias"]
    params["params"]["cell"]["out_proj"]["bias"] = bias.at[EOS_ID].add(2.0)

    kind = jax.devices()[0].device_kind
    backend = jax.default_backend()
    print(f"bench_serving: backend={backend} capacity={capacity} K={K} "
          f"T={max_len} dtype={dtype}", file=sys.stderr)

    def requests_for(trace) -> list[ClipRequest]:
        out = []
        for item in trace.items:
            feats, masks = synth_request_features(item, modal)
            out.append(ClipRequest(
                req_id=item.req_id, feats=feats, masks=masks,
                seed=item.seed, arrival_s=item.arrival_s,
            ))
        return out

    def service() -> CaptionService:
        return CaptionService(
            model, params, capacity=capacity, num_rollouts=K,
            max_len=max_len, stride=stride,
        )

    # ---- warmup + solo calibration ----------------------------------------
    # ONE continuous service serves every trace (an always-on service never
    # re-compiles per trace), warmed over both frame buckets; the static
    # policy gets one pre-warmed fixed-shape decode for the same reason —
    # neither policy's measurements pay compile time.
    frame_mix = (max(frames // 4, 1), frames)
    warm_spec = TrafficSpec(kind="poisson", rate_rps=100.0, num_requests=4,
                            seed=99, frame_choices=frame_mix)
    warm_reqs = requests_for(make_trace(warm_spec))
    svc = service()
    svc.serve(warm_reqs[:3])             # compile encode buckets + stride
    static_decode = jax.jit(
        lambda p, f, m, r: fused_decode(
            model, p, f, m, r, num_rollouts=K, max_len=max_len,
        )
    )
    static_batch_serve(
        model, params, requests_for(make_trace(warm_spec))[:capacity],
        capacity=capacity, num_rollouts=K, max_len=max_len,
        decode_fn=static_decode,
    )
    t0 = time.perf_counter()
    solo_rep = svc.serve([warm_reqs[3]])
    solo = max(
        (time.perf_counter() - t0),
        max(r.latency_s for r in solo_rep.results.values()),
    )
    slo_s = args.slo_factor * solo
    # arm the engine's burn-rate monitor with the calibrated SLO: the bench's
    # goodput gate and the live serving.slo.* gauges judge the same target
    svc.set_slo(slo_s)
    rate = args.load * capacity / solo
    print(f"bench_serving: solo={solo * 1e3:.1f}ms slo={slo_s * 1e3:.1f}ms "
          f"rate={rate:.2f}rps", file=sys.stderr)

    specs = {
        "poisson": TrafficSpec(
            kind="poisson", rate_rps=rate, num_requests=n_req, seed=7,
            frame_choices=frame_mix,
        ),
        # bursts sized to ~half a batch: real traffic does not arrive in
        # batch-size quanta, which is exactly the static former's weakness
        # (partial batches wait across the quiet window for stragglers)
        "bursty": TrafficSpec(
            kind="bursty", rate_rps=rate, num_requests=n_req, seed=11,
            burst_factor=4.0,
            burst_len_s=max(capacity / (2 * 4.0 * rate), 1e-3),
            frame_choices=frame_mix,
        ),
    }

    traces_out: dict[str, dict] = {}
    parity_ok = True
    parity_checked = 0
    stride_cost = None
    for name, spec in specs.items():
        trace = make_trace(spec)
        cont = svc.serve(requests_for(trace), realtime=True)
        if stride_cost is None:
            stride_cost = svc.stride_cost()
        static = static_batch_serve(
            model, params, requests_for(trace), capacity=capacity,
            num_rollouts=K, max_len=max_len, realtime=True,
            decode_fn=static_decode,
        )
        cs, ss = (_policy_stats(cont, trace, slo_s),
                  _policy_stats(static, trace, slo_s))
        traces_out[name] = {
            "spec": {
                "kind": spec.kind, "rate_rps": round(spec.rate_rps, 4),
                "num_requests": spec.num_requests, "seed": spec.seed,
                "frame_choices": list(spec.frame_choices),
            },
            "continuous": cs,
            "static": ss,
            "goodput_ratio_cont_vs_static": (
                round(cs["goodput_rps"] / ss["goodput_rps"], 3)
                if ss["goodput_rps"] else None
            ),
        }
        print(f"bench_serving: {name} continuous p50={cs['p50_s']}s "
              f"p99={cs['p99_s']}s goodput={cs['goodput_rps']}rps | "
              f"static p50={ss['p50_s']}s p99={ss['p99_s']}s "
              f"goodput={ss['goodput_rps']}rps", file=sys.stderr)

        # in-run parity: served output == offline fused decode, bitwise
        for req in requests_for(trace)[:3]:
            res = cont.results[req.req_id]
            pad = frames - req.num_frames
            f1 = {
                m: jnp.asarray(np.pad(req.feats[m], ((0, pad), (0, 0)))[None])
                for m in req.feats
            }
            m1 = {
                m: jnp.asarray(np.pad(req.masks[m], ((0, pad),))[None])
                for m in req.masks
            }
            g, gl, s, sl = jax.tree.map(np.asarray, fused_decode(
                model, params, f1, m1, jax.random.key(req.seed),
                num_rollouts=K, max_len=max_len,
            ))
            off_tok = np.concatenate([g, s[:, 0]], axis=0)
            off_lp = np.concatenate([gl, sl[:, 0]], axis=0)
            parity_ok = parity_ok and bool(
                np.array_equal(res.tokens, off_tok)
                and np.array_equal(res.logprobs, off_lp)
            )
            parity_checked += 1

    # ---- admission-group parity -------------------------------------------
    # grouped admission encode must be ROW-stable: at f32 a batched encoder
    # pass admits the same bits as per-request admission (pinned bit-exact
    # here and in tests/test_serving.py); at bf16 the batched pass can
    # legitimately drift (reduction order inside the matmuls changes with
    # the batch dim), which is WHY the engine falls back to per-request
    # encode at bf16 — the drift is measured and bounded here, mirroring
    # the decode kernel's bf16 parity story
    ag_n = 4
    ag_spec = TrafficSpec(kind="poisson", rate_rps=1e9, num_requests=ag_n,
                          seed=23, frame_choices=(frames,))
    if dtype == "float32":
        grouped, solo_adm = (
            CaptionService(
                model, params, capacity=ag_n, num_rollouts=K,
                max_len=max_len, stride=stride, admit_group=g,
            ).serve(requests_for(make_trace(ag_spec)))
            for g in (ag_n, 1)
        )
        ag_f32_exact = all(
            np.array_equal(grouped.results[rid].tokens,
                           solo_adm.results[rid].tokens)
            and np.array_equal(grouped.results[rid].logprobs,
                               solo_adm.results[rid].logprobs)
            for rid in grouped.results
        )
        model_bf = CaptionModel(dataclasses.replace(cfg, dtype="bfloat16"))
    else:
        ag_f32_exact = (
            "skipped: bf16 operating point — grouped f32 admission is "
            "pinned by tests/test_serving.py and the smoke run"
        )
        model_bf = model
    # the engine refuses grouped admission at bf16 (falls back to 1)
    ag_bf16_fallback = CaptionService(
        model_bf, params, capacity=ag_n, num_rollouts=K, max_len=max_len,
        stride=stride, admit_group=ag_n,
    )
    bf16_fell_back = (ag_bf16_fallback.requested_admit_group == ag_n
                      and ag_bf16_fallback.admit_group == 1)
    # measure the batched-vs-solo bf16 encoder drift the fallback avoids
    enc_bf = jax.jit(lambda p, f, m: model_bf.apply(
        p, f, m, method=CaptionModel.encode
    ))
    ag_reqs = requests_for(make_trace(ag_spec))
    feats_b = {
        name: jnp.asarray(np.stack(
            [np.asarray(r.feats[name], np.float32) for r in ag_reqs]
        )) for name, _ in modal
    }
    masks_b = {
        name: jnp.asarray(np.stack(
            [np.asarray(r.masks[name], np.float32) for r in ag_reqs]
        )) for name, _ in modal
    }
    enc_batched = enc_bf(params, feats_b, masks_b)
    bf16_drift = bf16_scale = 0.0
    for i in range(ag_n):
        enc_solo = enc_bf(
            params,
            {k: v[i:i + 1] for k, v in feats_b.items()},
            {k: v[i:i + 1] for k, v in masks_b.items()},
        )
        for a, b in ((enc_batched.memory[i:i + 1], enc_solo.memory),
                     (enc_batched.memory_proj[i:i + 1],
                      enc_solo.memory_proj)):
            a32 = np.asarray(a, np.float32)
            b32 = np.asarray(b, np.float32)
            bf16_drift = max(bf16_drift, float(np.max(np.abs(a32 - b32))))
            bf16_scale = max(bf16_scale, float(np.max(np.abs(b32))))
    bf16_tol = 0.05  # a few bf16 ulps relative to the encoder output scale
    bf16_within = bf16_drift <= bf16_tol * max(bf16_scale, 1e-9)

    # ---- paged in-kernel attention rung -----------------------------------
    # the same stride kernel, paged (in-kernel page-table DMA, no dense
    # bank) vs its own dense-gather reference (paged=False), on both trace
    # shapes. Off-TPU the kernel runs in interpret mode — far slower per
    # stride than compiled Mosaic — so the rung shrinks its traces there;
    # the paged-vs-gather comparison (same kernel math, same requests, one
    # reading pages in-kernel, one through gather_bank) is exact everywhere.
    m_pal = CaptionModel(dataclasses.replace(cfg, decode_impl="pallas"))
    paged_n = n_req if backend == "tpu" else max(4, n_req // 6)
    svc_paged = CaptionService(
        m_pal, params, capacity=capacity, num_rollouts=K, max_len=max_len,
        stride=stride,
    )
    svc_gather = CaptionService(
        m_pal, params, capacity=capacity, num_rollouts=K, max_len=max_len,
        stride=stride, paged=False,
    )
    print("bench_serving: warming paged_inkernel + dense_gather rungs",
          file=sys.stderr)
    svc_paged.serve(warm_reqs[:3])
    svc_gather.serve(warm_reqs[:3])
    paged_traces: dict[str, dict] = {}
    paged_parity_ok = True
    paged_checked = 0
    for name, spec in specs.items():
        pspec = dataclasses.replace(spec, num_requests=paged_n)
        trace = make_trace(pspec)
        rep_p = svc_paged.serve(requests_for(trace), realtime=True)
        rep_g = svc_gather.serve(requests_for(trace), realtime=True)
        ps = _policy_stats(rep_p, trace, slo_s)
        gs = _policy_stats(rep_g, trace, slo_s)
        # the in-run parity gate: identical math on identical bytes —
        # token AND logprob bit-exact, per request, both traces
        for rid in rep_p.results:
            rp, rg = rep_p.results[rid], rep_g.results[rid]
            paged_parity_ok = paged_parity_ok and bool(
                np.array_equal(rp.tokens, rg.tokens)
                and np.array_equal(rp.logprobs, rg.logprobs)
            )
            paged_checked += 1
        paged_traces[name] = {
            "num_requests": paged_n,
            "paged_inkernel": ps,
            "dense_gather": gs,
            "goodput_ratio_paged_vs_gather": (
                round(ps["goodput_rps"] / gs["goodput_rps"], 3)
                if gs["goodput_rps"] else None
            ),
        }
        print(f"bench_serving: {name} paged p50={ps['p50_s']}s "
              f"goodput={ps['goodput_rps']}rps | gather p50={gs['p50_s']}s "
              f"goodput={gs['goodput_rps']}rps", file=sys.stderr)
    bank_itemsize = int(svc_paged.bank.mem.dtype.itemsize) \
        if svc_paged.bank.mem is not None else 4
    bank_paged = serving_bank_bytes_per_stride(
        capacity, svc_paged.W, d_embed, d_att, bank_itemsize, paged=True
    )
    bank_dense = serving_bank_bytes_per_stride(
        capacity, svc_paged.W, d_embed, d_att, bank_itemsize, paged=False
    )

    # stress: a pool TWICE one batch's dense-bank footprint. The gather
    # path refuses it at construction (it re-materializes every lane's
    # full window per stride); the paged engine admits it and the
    # encode-ahead staging drives the page high-water mark past the
    # footprint while every request still completes.
    stress_cap, stress_page = 2, 2
    stress_ppr = -(-len(modal) * frames // stress_page)
    stress_pages = 2 * stress_cap * stress_ppr
    svc_stress = CaptionService(
        m_pal, params, capacity=stress_cap, num_rollouts=1,
        max_len=max_len, stride=stride, frame_bucket=1,
        page_size=stress_page, num_pages=stress_pages,
    )
    stress_reqs = requests_for(make_trace(TrafficSpec(
        kind="poisson", rate_rps=1e9, num_requests=6, seed=31,
        frame_choices=(frames,),
    )))
    stress_rep = svc_stress.serve(stress_reqs)
    stress_footprint = stress_cap * svc_stress.table_width
    hwm_exceeds = svc_stress.bank.pages_hwm > stress_footprint
    try:
        CaptionService(
            model, params, capacity=stress_cap, num_rollouts=1,
            max_len=max_len, stride=stride, frame_bucket=1,
            page_size=stress_page, num_pages=stress_pages,
        )
        gather_refuses = False
    except ValueError:
        gather_refuses = True
    print(f"bench_serving: stress pool={stress_pages} pages "
          f"(dense footprint {stress_footprint}) hwm="
          f"{svc_stress.bank.pages_hwm} gather_refuses={gather_refuses}",
          file=sys.stderr)

    feat_dims = tuple(d for _, d in modal)
    _, per_tok = enc_and_per_tok_flops(
        frames, d_embed, d_hidden, d_att, vocab_n, feat_dims, 1
    )
    analytic_stride = capacity * (1 + K) * stride * per_tok
    try:
        peak = peak_flops(kind)
    except KeyError:
        peak = None  # no published peak (the CPU smoke): MFU not measured
    cont_p = traces_out["poisson"]["continuous"]
    mfu_flops = (stride_cost or {}).get("flops", analytic_stride)
    serving_mfu = (
        cont_p["strides"] * mfu_flops / cont_p["makespan_s"] / peak
        if cont_p["makespan_s"] and peak else None
    )

    beats = {
        name: bool(
            t["continuous"]["goodput_rps"] > t["static"]["goodput_rps"]
        )
        for name, t in traces_out.items()
    }
    if args.smoke:
        ok = parity_ok and ag_f32_exact is True and bf16_fell_back \
            and bf16_within and all(
                t["continuous"]["goodput_rps"] > 0
                for t in traces_out.values()
            )
        if not ok:
            sys.exit(
                "bench_serving: SMOKE FAILURE — parity, admission-group, "
                f"or goodput gate failed: parity={parity_ok}, "
                f"admit_group_f32={ag_f32_exact}, "
                f"bf16_fallback={bf16_fell_back}, "
                f"bf16_drift_within_tol={bf16_within}, traces={traces_out}"
            )
        # the paged gate is FATAL in-run: the in-kernel page reader must be
        # bit-exact vs the dense-gather reference, and the oversized pool
        # must genuinely fill past the dense footprint the gather refuses
        if not (paged_parity_ok and hwm_exceeds and gather_refuses
                and stress_rep.completed == len(stress_reqs)):
            sys.exit(
                "bench_serving: SMOKE FAILURE — paged in-kernel gate: "
                f"paged_vs_gather_bit_exact={paged_parity_ok} "
                f"(over {paged_checked} requests), "
                f"hwm_exceeds_dense_footprint={hwm_exceeds} "
                f"(hwm={svc_stress.bank.pages_hwm} vs {stress_footprint}), "
                f"gather_refuses_pool={gather_refuses}, "
                f"stress_completed={stress_rep.completed}/"
                f"{len(stress_reqs)}"
            )
        # the SLO monitor must have judged the served traffic: target gauge
        # armed by set_slo() and per-window attainment/burn-rate populated
        from cst_captioning_tpu.obs import metrics as obs_metrics
        gauges = obs_metrics.snapshot()["gauges"]
        slo_gauges = ("serving.slo.target_s", "serving.slo.attainment.60s",
                      "serving.slo.burn_rate.60s")
        missing = [g for g in slo_gauges if gauges.get(g) is None]
        if missing or gauges["serving.slo.target_s"] <= 0.0:
            sys.exit(
                "bench_serving: SMOKE FAILURE — SLO gauges not populated: "
                f"missing={missing}, "
                f"target_s={gauges.get('serving.slo.target_s')}"
            )

    out = {
        "metric": "serving_request_latency_and_slo_goodput",
        "capacity": capacity,
        "rollouts": K,
        "max_len": max_len,
        "stride": stride,
        "requests_per_trace": n_req,
        "dtype": dtype,
        "device_kind": kind,
        "backend": backend,
        "smoke": bool(args.smoke),
        "solo_latency_s": round(solo, 4),
        "slo_s": round(slo_s, 4),
        "slo_factor": args.slo_factor,
        "slo_monitor": svc.slo_snapshot(),
        "offered_load": args.load,
        "traces": traces_out,
        "parity": {
            "continuous_vs_offline_bit_exact": parity_ok,
            "checked_requests": parity_checked,
            "admit_group_size": ag_n,
            "admit_group_f32_bit_exact": ag_f32_exact,
            "admit_group_bf16_fallback_engaged": bf16_fell_back,
            "admit_group_bf16_encode_max_drift": bf16_drift,
            "admit_group_bf16_drift_tol_frac": bf16_tol,
            "admit_group_bf16_drift_within_tol": bool(bf16_within),
        },
        "flops": {
            "per_stride_hlo": (stride_cost or {}).get("flops"),
            "per_stride_analytic": round(analytic_stride),
            "backend": "xla_hlo" if stride_cost else "analytic",
            "serving_decode_mfu_poisson": (
                None if serving_mfu is None else round(serving_mfu, 8)
            ),
            "assumed_peak_bf16_flops": peak,
        },
        "paged": {
            "requests_per_trace": paged_n,
            "traces": paged_traces,
            "per_stride_bank_bytes": {
                "paged_inkernel": bank_paged,
                "dense_gather": bank_dense,
                "bytes_avoided_frac": round(1.0 - bank_paged / bank_dense, 4),
            },
            "parity": {
                "paged_vs_gather_bit_exact": paged_parity_ok,
                "checked_requests": paged_checked,
            },
            "stress": {
                "pool_pages": stress_pages,
                "dense_footprint_pages": stress_footprint,
                "pages_hwm": int(svc_stress.bank.pages_hwm),
                "completed": stress_rep.completed,
                "requests": len(stress_reqs),
            },
        },
        "acceptance": {
            "continuous_beats_static_goodput": beats,
            "paged_matches_dense_gather_bit_exact": bool(paged_parity_ok),
            "paged_pool_exceeds_dense_footprint": bool(hwm_exceeds),
            "gather_path_refuses_oversized_pool": bool(gather_refuses),
        },
        "note": (
            None if backend == "tpu" else
            "non-TPU run: absolute latencies are CPU-bound and the stride "
            "dispatch overhead is proportionally larger than on TPU, so "
            "p50/p99 here are not the flagship numbers — regenerate on TPU. "
            "The continuous-vs-static comparison (same hardware, same "
            "seeded trace, same SLO in the same run) is meaningful "
            "everywhere; the SLO self-calibrates to the machine via the "
            "measured solo latency."
        ),
    }
    print(json.dumps(out))
    path = args.json or ("" if args.smoke else "BENCH_SERVING.json")
    if path:
        with open(path, "w") as f:
            json.dump(out, f, indent=2)
        print(f"bench_serving: wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()
