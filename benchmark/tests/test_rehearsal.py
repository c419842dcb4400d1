"""The CPU rehearsal: every job's ``run`` walked end to end at a tiny
configuration (``tiny.py``), on one device and, for ``cst``, on four virtual
devices. It shows control flow and correctness checks; never a speed."""

import importlib

import numpy as np
import pytest

from benchmark.tests import tiny


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


def _run(workload, config, cache, chips, trace=False):
    ctx = tiny.Ctx(workload, config, cache, chips=chips, trace=trace)
    job = importlib.import_module("benchmark.jobs." + workload["job"])
    return ctx, job.run(ctx)


def _manifest():
    import json
    import os

    from benchmark import run as bench_run

    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _per_layer(ctx, res, wall_minus_perf, compiles=0, cell=None, trace=None):
    """The traced run's per-layer line as ``run.py`` builds it: without a
    device trace (a CPU has none worth reading), or with ``trace`` standing
    in for the chip's, read as the manifest's cell ``cell``."""
    from benchmark import run as bench_run

    manifest = _manifest()
    cell = cell or manifest["workloads"][0]
    return bench_run.per_layer_metrics(manifest, cell["name"], {
        "result": res, "trace": trace, "window": (ctx.t_open, ctx.t_close),
        "spans": bench_run.program_spans(ctx.obs_dir, wall_minus_perf),
        "trace_window": (ctx.t_open, ctx.t_close) if trace else None,
        "compiles_in_window": compiles,
        "config": ctx.config, "workload": ctx.workload,
        "chips": cell["chips"] if trace else ctx.chips,
        "device_kind": "TPU v5 lite" if trace else "cpu",
        "memory_peak_bytes": 2**30 if trace else 0,
    })


@pytest.mark.parametrize("chips", [1, 4])
def test_cst_job(cache, chips):
    import jax

    if len(jax.devices()) < chips:
        pytest.skip("needs four virtual devices")
    ctx, res = _run(tiny.CST, tiny.CONFIG, cache, chips)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 3
    assert res["end_to_end"]["clips_per_s_per_chip"] > 0
    steps = res["steps"]
    t0, t1 = ctx.t_open, ctx.t_close
    assert t1 - t0 >= ctx.seconds
    assert all(t0 < s[0] <= t1 for s in steps) and steps[-1][0] == t1
    # 96 videos = 3 steps of 32 an epoch; the window holds whole epochs
    assert {s[1] for s in steps} == {32.0} and len(steps) % 3 == 0
    ch = res["checks"]
    # f32 model against the f32 reference
    assert ch["logprob_mean_abs_diff"] < 1e-5
    assert ch["scorer"] == "native" and ch["trainer_scorer_native"]
    assert ch["params_moved"] > 0
    if chips == 4:
        a, b = ch["mesh_vs_one_grad_norm"]
        assert abs(a - b) <= 1e-4 * abs(b)


@pytest.fixture(scope="module")
def traced(cache):
    """One ``--trace 1`` rehearsal of ``cst`` (the program's obs recorder is
    one per process, so one traced run a module): the job's context and
    result, the clock offset of its spans, the compiles in its window."""
    import time

    from jax import monitoring

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(time.perf_counter())
        if event.endswith("backend_compile_duration") else None)
    wall_minus_perf = time.time() - time.perf_counter()
    ctx, res = _run(tiny.CST, tiny.CONFIG, cache, 1, trace=True)
    in_window = [t for t in compiles if ctx.t_open < t <= ctx.t_close]
    return ctx, res, wall_minus_perf, compiles, in_window


def test_cst_job_traced_feeds_the_readers(traced):
    """With the program's obs spans on (a ``--trace 1`` run): the host-side
    per-layer metrics read what the job returns, the device-side ones find
    nothing and are left out, and nothing compiled inside the window."""
    ctx, res, wall_minus_perf, compiles, in_window = traced
    assert res["correct"]
    assert compiles and not in_window      # the epoch keys were warmed
    got = _per_layer(ctx, res, wall_minus_perf, len(in_window))
    assert set(got) == {
        "compiles_in_window", "input_wait_ms_per_step", "step_p50_ms",
        "epoch_turnover_ms", "decode_wait_ms_per_step", "reward_ms_per_step",
        "caption_len_mean"}
    v = {k: m["value"] for k, m in got.items()}
    n, window_ms = len(res["steps"]), 1e3 * (ctx.t_close - ctx.t_open)
    # every next() of the window, and the n/3 - 1 turnovers inside it, are
    # main-thread time: together they fit into the window
    assert 0 < v["input_wait_ms_per_step"] * n < window_ms
    assert 0 < v["epoch_turnover_ms"] * (n // 3 - 1) < window_ms
    assert v["input_wait_ms_per_step"] * n + v["epoch_turnover_ms"] * (
        n // 3 - 1) < window_ms
    # the reward span splits into the wait for the decode and the scoring
    spans = [s for s in training_spans(ctx) if s["name"] == "rl.reward"]
    assert len(res["marks"]["decode_ready"]) >= len(spans) > n
    assert v["reward_ms_per_step"] > 0 and v["decode_wait_ms_per_step"] >= 0
    mean_span_ms = 1e3 * sum(s["dur"] for s in spans) / len(spans)
    assert v["reward_ms_per_step"] + v["decode_wait_ms_per_step"] == \
        pytest.approx(mean_span_ms, rel=0.5)


@pytest.mark.parametrize("cell", _manifest()["workloads"],
                         ids=lambda w: w["name"])
def test_traced_line_has_every_metric_of_the_cell(traced, cell):
    """The driver refuses a ``--trace 1`` line that lacks a per-layer metric
    the manifest gives the cell (PR 22's second round was refused for
    ``allreduce_ms_per_step`` on one chip). So: the rehearsal's result, read
    with a device trace of the cell's shape (its chips, the job's two
    programs, an all-reduce on a mesh), yields every metric of the cell, and
    none that the manifest does not give it."""
    from benchmark import run as bench_run
    from benchmark import trace_reduce
    from benchmark.tests.test_trace_reduce import _trace

    ctx, res, wall_minus_perf, _, in_window = traced
    if _workload_file(cell)["job"] != tiny.CST["job"]:
        pytest.skip("the traced rehearsal walks cst; another job's cell "
                    "brings a case of its own")
    summary = trace_reduce.reduce_trace(_trace(cell["chips"]))
    # a CPU keeps no memory_stats(): the chip's readings stand in
    res = dict(res, hbm={"peak_at_open": 2**32, "peak_at_close": 2**32,
                         "live_max": 2**30})
    got = _per_layer(ctx, res, wall_minus_perf, len(in_window), cell=cell,
                     trace=summary)
    want = bench_run.metrics_of(_manifest(), "per_layer", cell["name"])
    assert set(got) == {m["name"] for m in want}
    assert all(np.isfinite(m["value"]) for m in got.values())
    assert ("allreduce_ms_per_step" in got) == (cell["chips"] > 1)


def _workload_file(cell):
    import json
    import os

    from benchmark import run as bench_run

    with open(os.path.join(bench_run.HERE, "workloads",
                           cell["name"] + ".json")) as f:
        return json.load(f)


def training_spans(ctx):
    from benchmark import training

    return training.read_spans(ctx.obs_dir)


def test_xe_job(cache):
    ctx, res = _run(tiny.XE, tiny.meanpool_config(), cache, 1)
    assert res["correct"] and res["attempted"] > 3
    ch = res["checks"]
    assert abs(ch["program_loss"] - ch["reference_loss"]) < 1e-5
    # 96 videos x 5 references = 480 rows = 7 x 64 + 32: the padded last
    # batch of an epoch counts its 32 valid rows only
    assert {s[1] for s in res["steps"]} == {64.0, 32.0}
    assert len(res["steps"]) % 8 == 0            # whole epochs
    assert sum(s[1] for s in res["steps"]) == 480 * len(res["steps"]) // 8
    t = np.array([s[0] for s in res["steps"]])
    assert np.all(np.diff(t) > 0)
    # one next() a step and one that finds each epoch at its end
    inside = [w for w in res["input_waits"]
              if ctx.t_open <= w[0] and w[1] <= ctx.t_close]
    assert len(inside) in range(len(t), len(t) + len(t) // 8 + 2)
    assert len(res["turnovers"]) >= len(t) // 8


def test_policy_length_check_fails_loudly():
    from benchmark import training

    long_rows = np.full((4, 12), 5)
    with pytest.raises(SystemExit, match="caption lengths"):
        training.check_policy_lengths(
            long_rows, long_rows,
            {"sampled_len_mean": [1.0, 9.0], "sampled_len_p99_max": 11})
