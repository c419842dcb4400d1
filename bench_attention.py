"""Attention micro-benchmark: XLA composite vs the Pallas fused kernel.

VERDICT r3 missing #3: ``ops/attention_pallas.py`` is parity-tested but had
no perf evidence in its own claimed regime ("M in the thousands"). This
bench times ONE decode-step attention context computation —

    q [B, d_att], memory [B, M, E], memory_proj [B, M, d_att], mask [B, M]
    -> context [B, E]

— for both implementations at frame counts M in {40, 512, 2048, 8192} (the
flagship model's M=40 = 2 modalities x 20 frames up through the long-context
regime the SP package exists for), in f32 and bf16, on whatever backend is
available (the recorded numbers come from the TPU v5e — see BASELINE.md
"Pallas attention kernel").

Dims match the flagship config: E=512 (d_embed), d_att=256.

Prints one JSON line per (M, dtype) with xla_ms / pallas_ms / speedup, then a
summary line with the crossover M (if any).

Usage: python bench_attention.py [--batch B] [--iters N] [--json PATH]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

M_SWEEP = (40, 512, 2048, 8192)
D_ATT = 256
D_EMBED = 512


def _make_loop(op, iters: int):
    """One jitted program chaining ``iters`` dependent attention calls.

    Per-dispatch host<->device latency would otherwise swamp a sub-
    millisecond op; the chain q -> ctx -> q' forces the iterations to run
    sequentially on device, so total/iters is the true per-op time plus one
    dispatch/iters.
    """
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(q, v, mem, proj, mask):
        def body(q, _):
            ctx = op(q, v, mem, proj, mask)
            qn = q + 1e-6 * ctx[:, : q.shape[1]].astype(q.dtype)
            return qn, ()
        qf, _ = jax.lax.scan(body, q, None, length=iters)
        return qf
    return run


def _time(fn, arg_variants, iters: int) -> float:
    """Per-op ms: best wall time of the ``iters``-chain / iters.

    Every timed call uses a DIFFERENT input and ends with a host readback
    of the result inside the timed region, so the clock stops on a value
    that depends on the whole chain; the readback's cost amortizes over the
    1000-iter chain.
    """
    out = fn(*arg_variants[0])
    float(np.asarray(out).ravel()[0])  # compile + warm
    times = []
    for a in arg_variants[1:]:
        t0 = time.perf_counter()
        out = fn(*a)
        float(np.asarray(out).ravel()[0])
        times.append((time.perf_counter() - t0) * 1e3 / iters)
    return float(min(times))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--iters", type=int, default=1000,
                    help="attention calls chained inside one dispatch (must "
                         "be large enough that the per-dispatch cost "
                         "divides away)")
    ap.add_argument("--json", default="", help="also write results to PATH")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.ops import fused_additive_attention
    from cst_captioning_tpu.ops.attention_pallas import _reference

    backend = jax.default_backend()
    kind = jax.devices()[0].device_kind
    print(f"bench_attention: backend={backend} device={kind} "
          f"B={args.batch} E={D_EMBED} d_att={D_ATT}", file=sys.stderr)
    if backend != "tpu":
        print("bench_attention: WARNING — not a TPU; the Pallas kernel runs "
              "in interpret mode and the numbers are meaningless for the "
              "crossover question", file=sys.stderr)

    # dispatch-floor estimate: wall time of a trivial chained program with a
    # distinct input + forced readback (see _time). Rows whose total time is
    # near this floor measure the dispatch, not the op.
    @jax.jit
    def _tiny(x):
        def body(c, _):
            return c + 1.0, ()
        return jax.lax.scan(body, x, None, length=args.iters)[0]

    floors = []
    for i in range(3):
        z = jnp.full((), float(i))
        t0 = time.perf_counter()
        float(np.asarray(_tiny(z)))
        floors.append((time.perf_counter() - t0) * 1e3)
    floor_ms = min(floors[1:])  # [0] includes compile
    print(f"bench_attention: dispatch floor ~{floor_ms:.1f}ms per chained "
          f"call ({args.iters} iters)", file=sys.stderr)

    xla_loop = _make_loop(_reference, args.iters)
    pallas_loop = _make_loop(
        lambda *a: fused_additive_attention(*a, 8, 128), args.iters
    )
    xla = jax.jit(_reference)
    pallas = jax.jit(fused_additive_attention, static_argnums=(5, 6))

    B = args.batch
    rng = np.random.default_rng(0)
    rows = []
    for dtype_name in ("float32", "bfloat16"):
        dtype = jnp.dtype(dtype_name)
        for M in M_SWEEP:
            v = jnp.asarray(rng.normal(size=(D_ATT,)), dtype)
            mem = jnp.asarray(rng.normal(size=(B, M, D_EMBED)), dtype)
            proj = jnp.asarray(rng.normal(size=(B, M, D_ATT)), dtype)
            mask = jnp.ones((B, M), jnp.float32)
            # 1 warmup + 3 timed variants, distinct q each (anti-caching)
            variants = [
                (jnp.asarray(rng.normal(size=(B, D_ATT)), dtype),
                 v, mem, proj, mask)
                for _ in range(4)
            ]
            a = variants[0]
            t_xla = _time(xla_loop, variants, args.iters)
            t_pal = _time(pallas_loop, variants, args.iters)
            # sanity: same math. Exact parity is pinned by
            # tests/test_ops_pallas.py in f32; bf16 inputs accumulate in a
            # different order between the two schedules, so the bf16 check is
            # only a gross-error tripwire
            tol = dict(rtol=1e-3, atol=1e-4) if dtype_name == "float32" \
                else dict(rtol=0.2, atol=0.2)
            np.testing.assert_allclose(
                np.asarray(xla(*a), np.float32),
                np.asarray(pallas(*a, 8, 128), np.float32), **tol,
            )
            row = {
                "M": M, "dtype": dtype_name,
                "xla_ms": round(t_xla, 4), "pallas_ms": round(t_pal, 4),
                "pallas_speedup": round(t_xla / t_pal, 3),
                # total chain time within 3x the dispatch floor: the row
                # measures host<->device latency, not the op — don't read a
                # winner out of it
                "at_dispatch_floor": bool(
                    min(t_xla, t_pal) * args.iters < 3.0 * floor_ms
                ),
            }
            rows.append(row)
            print(json.dumps(row))

    # crossover: smallest M where pallas wins for each dtype
    summary = {"metric": "attention_pallas_crossover", "backend": backend,
               "device_kind": kind, "batch": B}
    for dtype_name in ("float32", "bfloat16"):
        # a "win" below +5% or at the dispatch floor is noise, not a crossover
        wins = [r["M"] for r in rows
                if r["dtype"] == dtype_name and r["pallas_speedup"] > 1.05
                and not r["at_dispatch_floor"]]
        summary[f"crossover_m_{dtype_name}"] = min(wins) if wins else None
    print(json.dumps(summary))
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"rows": rows, "summary": summary}, f, indent=2)


if __name__ == "__main__":
    main()
