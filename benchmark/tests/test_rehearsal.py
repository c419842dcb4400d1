"""The CPU rehearsal: every configuration of the manifest walked through its
cells' jobs at its own ``tiny`` sizes (``tiny.py``), on one device and, for a
four-chip cell, on four virtual devices: reference, checks, cost model and
every reader of the cell, through the loaders ``run.py`` uses. A second
architecture that exists only as files under ``tests/second_architecture``
walks the same way, with no edit to a shared file. The jobs no cell lists yet
(``xe``, ``eval``) are walked on both, and for every (job, configuration) the
traced line holds exactly the metrics the manifest gives a cell of that job.
Nothing here takes the committed cells to be ``cst`` cells or the only ones:
a cell a later PR commits, of whatever job the harness has, is walked by the
same cases. It shows control flow and correctness checks; never a speed."""

import importlib

import numpy as np
import pytest

from benchmark import run as bench_run
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


def _run(workload, config, cache, chips, trace=False):
    """A job's ``run`` and the harness's ``settle`` behind it, as ``run.py``
    calls them (without its look for a chip)."""
    ctx = tiny.Ctx(workload, config, cache, chips=chips, trace=trace,
                   seconds=SECONDS.get(workload["job"], 1.5))
    job = importlib.import_module("benchmark.jobs." + workload["job"])
    return ctx, bench_run.settle(job.run(ctx), ctx.log)


# the XLA module the one program of a job other than ``cst`` shows up as in
# the stand-in trace; with ``cst``, the jobs these tests know
PROGRAM = {"xe": "xe_step", "eval": "_lambda_"}
JOBS = ("cst", *PROGRAM)


# job ``xe`` holds its loss to fall from the window's first quarter to its
# last (``loss_fall_slack``): over 60-120 steps of a 1.5 s window batch noise
# decides that (-0.040 to +0.0202 against 0.02 on eight runs), over 200-330
# steps it does not (-0.011 to +0.0049): more work, not a wider limit
SECONDS = {"xe": 5.0}


def _walks():
    """(configuration at its tiny sizes, workload, chips, the manifest's cell
    or None) for every cell of the manifest, and for the second architecture."""
    out = []
    for cell in tiny.manifest()["workloads"]:
        config = tiny.config_file(cell["config"])
        workload = tiny.workload_file(cell["name"])
        if workload["job"] not in JOBS:
            continue    # a job a later PR brought: its own test file walks it
        out.append(pytest.param(
            (tiny.tiny_config(config),
             tiny.tiny_workload(config, workload["job"], workload),
             cell["chips"], cell), id=cell["name"]))
    second = tiny.second_architecture()
    out.append(pytest.param((second, tiny.tiny_workload(second, "cst"), 1, None),
                            id="second_architecture.cst"))
    return out


# a CPU keeps no memory_stats(): a chip's readings stand in
CHIP_HBM = {"peak_at_open": 2**32, "peak_at_close": 2**32, "live_max": 2**30}


def _per_layer(ctx, res, wall_minus_perf, compiles=0, cell=None, trace=None,
               manifest=None):
    """The traced run's per-layer line as ``run.py`` builds it: without a
    device trace (a CPU has none worth reading), or with ``trace`` standing
    in for the chip's, read as the cell ``cell`` (a cell the manifest does
    not list gets the metrics that carry no ``workloads`` list: what a new
    one-chip cell is given)."""
    manifest = manifest or tiny.manifest()
    cell = cell or {"name": "a_new.one_chip_cell", "chips": 1}
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


@pytest.mark.parametrize("walk", _walks())
def test_cell_walks_its_job(cache, walk):
    import jax

    config, workload, chips, _cell = walk
    if len(jax.devices()) < chips:
        pytest.skip("needs four virtual devices")
    ctx, res = _run(workload, config, cache, chips)
    assert res["correct"] and res["failed"] == 0 and res["attempted"] > 3, \
        res["compared"]
    assert res["end_to_end"]["clips_per_s_per_chip"] > 0
    steps = res["steps"]
    t0, t1 = ctx.t_open, ctx.t_close
    assert t1 - t0 >= ctx.seconds
    assert all(t0 < s[0] <= t1 for s in steps)
    if workload["job"] != "cst":
        return      # what another job's window holds: its own case below
    assert steps[-1][0] == t1
    # 96 videos = 3 steps of 32 an epoch; the window holds whole epochs
    assert {s[1] for s in steps} == {32.0} and len(steps) % 3 == 0
    ch, cmp = res["checks"], res["compared"]
    assert all(r["ok"] for r in cmp.values())
    # an f32 model against the f32 reference, by the configuration's own
    # reference module and at its own tolerances
    assert cmp["decode_logprob_mean_abs_diff"]["limit"] == \
        config["checks"]["logprob_mean_abs_tol"]["value"]
    assert ch["logprob_mean_abs_diff"] < 2e-5
    assert ch["scorer"] == "native" and ch["trainer_scorer_native"]
    assert ch["params_moved"] > 0
    # the window's own update, followed by the reference after the window
    n = config["checks"]["follow_steps"]["value"]
    assert [k for k in cmp if k.startswith("rl_loss_step")] == [
        f"rl_loss_step{i}_abs_diff" for i in range(1, n + 1)]
    assert cmp["rl_loss_step1_abs_diff"]["limit"] == \
        config["checks"]["rl_loss_abs_tol"]["value"]
    assert {"first_grad_worst_leaf_gap", "first_grad_rel_diff",
            "param_change_worst_leaf_gap", "sampled_token_id_max"} <= set(cmp)
    assert any("the reference followed" in line for line in ctx.lines)
    assert any("the window's last decode" in line for line in ctx.lines)
    if chips == 4:
        a, b = ch["mesh_vs_one_grad_norm"]
        assert abs(a - b) <= 1e-4 * abs(b)
        assert "mesh_vs_one_grad_norm_rel_diff" in cmp


@pytest.fixture(scope="module",
                params=[w for w in _walks() if w.values[0][2] == 1
                        and w.values[0][1]["job"] == "cst"],
                ids=lambda w: w[3]["name"] if w[3] else "second_architecture")
def traced(cache, request):
    """One ``--trace 1`` rehearsal of ``cst`` for each architecture (the
    program's obs recorder is re-pointed by each): the job's context and
    result, the clock offset of its spans, the compiles in its window."""
    import time

    from jax import monitoring

    config, workload, _chips, cell = request.param
    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compiles.append(time.perf_counter())
        if event.endswith("backend_compile_duration") else None)
    wall_minus_perf = time.time() - time.perf_counter()
    ctx, res = _run(workload, config, cache, 1, trace=True)
    in_window = [t for t in compiles if ctx.t_open < t <= ctx.t_close]
    return ctx, res, wall_minus_perf, compiles, in_window, cell


def _read_as(own):
    """(manifest, cell, what its RL-step metrics' names end in): a committed
    cell reads under the manifest as it stands; the second architecture's
    walk as the cell a later PR would add for it (``tiny.with_a_cst_cell``),
    whose RL-step entries are ``<metric>.pooled_lstm`` and have no reader
    file of their own."""
    if own is not None:
        manifest = tiny.manifest()
        # a cell committed after the two accepted ones has the RL step's
        # entries under names of its own: what follows the reader's name
        tail = next(m["name"][len("decode_roofline"):]
                    for m in manifest["per_layer"]
                    if m["name"].split(".")[0] == "decode_roofline"
                    and own["name"] in m["workloads"])
        return manifest, own, tail
    made = tiny.with_a_cst_cell(tiny.manifest())
    return made, made["workloads"][-1], ".pooled_lstm"


def test_cst_job_traced_feeds_the_readers(traced):
    """With the program's obs spans on (a ``--trace 1`` run): the host-side
    per-layer metrics read what the job returns, the device-side ones find
    nothing and are left out, and nothing compiled inside the window."""
    ctx, res, wall_minus_perf, compiles, in_window, own = traced
    assert res["correct"], res["compared"]
    assert compiles and not in_window      # the epoch keys were warmed
    manifest, cell, tail = _read_as(own)
    got = _per_layer(ctx, res, wall_minus_perf, len(in_window), cell=cell,
                     manifest=manifest)
    assert {"compiles_in_window", "input_wait_ms_per_step", "step_p50_ms",
            "epoch_turnover_ms" + tail, "decode_wait_ms_per_step" + tail,
            "reward_ms_per_step" + tail, "caption_len_mean"} <= set(got)
    assert not {"decode_roofline" + tail, "update_roofline" + tail,
                "device_idle_share", "decode_device_ms_per_step" + tail} & set(got)
    # whatever the cell's own entries are called, the checks below read them
    # under the readers' names
    v = {k.removesuffix(tail) if tail else k: m["value"] for k, m in got.items()}
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


@pytest.mark.parametrize("chips", [1, 4])
def test_traced_line_has_every_metric_of_the_cell(traced, chips):
    """The driver refuses a ``--trace 1`` line that lacks a per-layer metric
    the manifest gives the cell (PR 22's second round was refused for
    ``allreduce_ms_per_step`` on one chip). So: the rehearsal's result, read
    with a device trace of the cell's shape (its chips, the job's two
    programs, an all-reduce on a mesh) and costed by the configuration's own
    cost model, yields every metric of the cell, all finite, and none that
    the manifest does not give it. The second architecture reads as the
    one-chip ``cst`` cell a later PR would add for it: the metrics that carry
    no ``workloads`` and its own entries of the RL step's, which are entries
    only (``<metric>.pooled_lstm``, read by ``<metric>``'s reader)."""
    from benchmark import trace_reduce
    from benchmark.tests.test_trace_reduce import _trace

    ctx, res, wall_minus_perf, _, in_window, own = traced
    if own is None:
        if chips != 1:
            pytest.skip("the second architecture walks one chip")
    elif own["chips"] != chips:
        # the walk on one device, read as the configuration's cell on four
        cells = [w for w in tiny.manifest()["workloads"]
                 if w["config"] == own["config"] and w["chips"] == chips
                 and tiny.workload_file(w["name"])["job"] == "cst"]
        if not cells:
            pytest.skip(f"no cst cell of {own['config']} on {chips} chip(s)")
        own = cells[0]
    manifest, cell, tail = _read_as(own)
    summary = trace_reduce.reduce_trace(_trace(cell["chips"]))
    res = dict(res, hbm=CHIP_HBM)
    got = _per_layer(ctx, res, wall_minus_perf, len(in_window), cell=cell,
                     trace=summary, manifest=manifest)
    want = tiny.assert_line_is_the_cells(got, manifest, cell["name"])
    assert all(np.isfinite(m["value"]) for m in got.values())
    assert ("allreduce_ms_per_step" in got) == (cell["chips"] > 1)
    assert 0 < got["decode_roofline" + tail]["value"] < 100
    assert 0 < got["update_roofline" + tail]["value"] < 100
    if own is None:
        listless = {m["name"] for m in manifest["per_layer"]
                    if "workloads" not in m}
        assert len(want - listless) == 13
        assert all(n.endswith(tail) for n in set(got) - listless)


def training_spans(ctx):
    from benchmark import training

    return training.read_spans(ctx.obs_dir)


@pytest.mark.parametrize("which", ["manifest", "second_architecture"])
def test_xe_job(cache, which):
    """``jobs/xe.py`` has no cell yet; it stays rehearsed, on the manifest's
    first configuration and on the second architecture, each by its own
    reference, tolerance and rows."""
    whole, config = _config_of(which)
    ctx, res = _run(tiny.tiny_workload(whole, "xe"), config, cache, 1)
    assert res["correct"] and res["attempted"] > 3, res["compared"]
    ch = res["checks"]
    assert abs(ch["program_loss"] - ch["reference_loss"]) < 2e-5
    assert res["compared"]["xe_loss_abs_diff"]["limit"] == \
        config["checks"]["loss_abs_tol"]["value"]
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


def _config_of(which):
    """(the file, the configuration at tiny sizes) of the manifest's first
    configuration or of the second architecture."""
    whole = (tiny.config_file(tiny.manifest()["configs"][0]["name"])
             if which == "manifest" else tiny.second_architecture())
    return whole, tiny.tiny_config(whole)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("which", ["manifest", "second_architecture"])
def test_eval_job(cache, which, chips):
    """``jobs/eval.py`` has no cell yet; it stays rehearsed on both
    configurations, on one device and on four virtual ones: the Evaluator's
    beam search pass after pass, the window on a pass's end, ``correct`` from
    the tokens the window's own decode emitted."""
    import jax

    if len(jax.devices()) < chips:
        pytest.skip("needs four virtual devices")
    whole, config = _config_of(which)
    ctx, res = _run(tiny.tiny_workload(whole, "eval"), config, cache, chips)
    assert res["correct"] and res["failed"] == 0, res["compared"]
    cmp = res["compared"]
    assert {"eval_token_id_max", "metric_table_finite"} <= set(cmp)
    for number, limit in (
            ("eval_beam_token_mismatch_share", "beam_token_mismatch_tol"),
            ("eval_beam_score_gap_mean", "beam_score_gap_tol"),
            ("eval_beam_rank_gap_max", "beam_rank_gap_tol"),
            ("eval_logprob_mean_abs_diff", "beam_logprob_mean_abs_tol")):
        assert cmp[number]["limit"] == config["checks"][limit]["value"]
    # 96 clips = 3 batches of 32 a pass; a step is a decoded batch, and the
    # window holds whole passes, opened and closed on a pass's end
    steps = res["steps"]
    assert res["attempted"] == len(steps) > 3 and len(steps) % 3 == 0
    assert {s[1] for s in steps} == {32.0}
    t0, t1 = ctx.t_open, ctx.t_close
    assert t1 - t0 >= ctx.seconds and all(t0 < s[0] <= t1 for s in steps)
    assert steps[-1][0] < t1            # the pass's scoring ends it
    assert res["end_to_end"]["clips_per_s_per_chip"] == pytest.approx(
        32.0 * len(steps) / (t1 - t0) / chips)
    # one next() a batch and one that finds each pass at its end
    inside = [w for w in res["input_waits"] if t0 <= w[0] and w[1] <= t1]
    assert len(inside) == len(steps) + len(steps) // 3
    shape = res["cost_shape"]
    assert (shape["kind"], shape["B"], shape["beam"]) == ("eval", 32, 5)
    assert shape["profile"]["lanes"][0] == 5 * 32
    assert 1.0 < res["caption_len_mean"] < 9.0
    assert any("the reference searched" in line for line in ctx.lines)


def _standin(chips, program):
    """``test_trace_reduce._trace`` with the job's program where the RL
    decode's module is."""
    from benchmark.tests.test_trace_reduce import _trace

    trace = _trace(chips)
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[0] = ev[0].replace("jit_decode", "jit_" + program)
    return trace


def _other_jobs():
    """(job, "manifest" | "second_architecture" | a committed cell's name):
    the jobs no ``cst`` cell runs, on the manifest's first configuration and
    on the second architecture, and every cell of such a job that a PR has
    committed since, under its own name."""
    out = [(job, which) for job in PROGRAM
           for which in ("manifest", "second_architecture")]
    for cell in tiny.manifest()["workloads"]:
        job = tiny.workload_file(cell["name"])["job"]
        if job in PROGRAM:
            out.append((job, cell["name"]))
    return out


@pytest.mark.parametrize("job, which", _other_jobs())
def test_traced_line_of_a_job_without_a_cell(cache, job, which):
    """For every (job, configuration) the traced line holds exactly the
    metrics the manifest gives a cell of that job (``cst``: the test above).
    A cell of ``xe`` or ``eval`` that no entry lists is given the metrics
    that carry no list, and every one of their readers finds something to
    read under the job; with the entries a PR would add for it
    (``tiny.with_an_eval_cell``) it is given those too, and reads them; and
    a cell that a PR has committed reads exactly what the manifest gives it."""
    import time

    from benchmark import trace_reduce

    manifest = tiny.manifest()
    committed = {w["name"]: w for w in manifest["workloads"]}.get(which)
    if committed is None:
        whole, config = _config_of(which)
        cell = {"name": f"{config['name']}.{job}", "chips": 1}
        workload = tiny.tiny_workload(whole, job)
    else:
        whole = tiny.config_file(committed["config"])
        config, cell = tiny.tiny_config(whole), dict(committed, chips=1)
        workload = tiny.tiny_workload(whole, job, tiny.workload_file(which))
    wall_minus_perf = time.time() - time.perf_counter()
    ctx, res = _run(workload, config, cache, 1, trace=True)
    assert res["correct"], res["compared"]
    summary = trace_reduce.reduce_trace(_standin(1, PROGRAM[job]))
    res = dict(res, hbm=CHIP_HBM)
    listless = {m["name"] for m in manifest["per_layer"] if "workloads" not in m}
    got = _per_layer(ctx, res, wall_minus_perf, cell=cell, trace=summary)
    assert tiny.assert_line_is_the_cells(got, manifest, cell["name"]) >= listless
    assert all(np.isfinite(m["value"]) for m in got.values())
    assert 0 < got["mfu_end_to_end"]["value"] < 100
    if job == "eval" and which == "second_architecture":
        made = tiny.with_an_eval_cell(manifest)
        assert made["workloads"][-1]["name"] == cell["name"]
        got = _per_layer(ctx, res, wall_minus_perf, cell=cell, trace=summary,
                         manifest=made)
        own = {m["name"] for m in made["per_layer"][len(manifest["per_layer"]):]}
        assert set(got) == listless | own and len(own) == 3
        by_reader = {k.split(".")[0]: m["value"] for k, m in got.items()}
        assert 0 < by_reader["eval_decode_roofline"] < 100
        assert by_reader["eval_score_ms_per_step"] > 0


def test_policy_length_check_fails_loudly():
    from benchmark import training

    long_rows = np.full((4, 12), 5)
    with pytest.raises(SystemExit, match="caption lengths"):
        training.check_policy_lengths(
            long_rows, long_rows,
            {"sampled_len_mean": [1.0, 9.0], "sampled_len_p99_max": 11})
