"""Observability subsystem: spans, metrics, Prometheus export, run reports.

Covers the obs/ acceptance surface: span nesting/ordering/self-time, the
thread-local context, histogram bucket math, the Prometheus textfile format,
report aggregation from a synthetic event file (the committed fixture
scripts/lint.sh also smokes), disabled-mode no-op (zero events, zero files),
profiler event routing, and a real chaos run whose report shows the
injected faults.
"""

from __future__ import annotations

import collections
import dataclasses
import json
import os
import time

import numpy as np
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.obs.metrics import Histogram, Registry, StepMeter
from cst_captioning_tpu.obs.report import (
    build_report,
    render_report,
    report_run,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURE_RUN = os.path.join(REPO, "tests", "fixtures", "obs_run")


@pytest.fixture(autouse=True)
def _clean_obs():
    """Obs state is process-global: every test starts and ends detached."""
    obs.shutdown()
    obs.REGISTRY.reset()
    yield
    obs.shutdown()
    obs.REGISTRY.reset()


def read_events(run_dir):
    with open(os.path.join(run_dir, "events.jsonl")) as f:
        return [json.loads(l) for l in f if l.strip()]


def spans_of(events, name=None):
    out = [e for e in events if e["event"] == "span"]
    return [e for e in out if e["name"] == name] if name else out


# ---- spans ------------------------------------------------------------------

def test_span_nesting_ordering_and_self_time(tmp_path):
    obs.configure(str(tmp_path / "run"), run="t")
    with obs.span("outer"):
        time.sleep(0.02)
        with obs.span("inner", tag="a"):
            time.sleep(0.03)
        time.sleep(0.0)
    obs.shutdown()
    events = read_events(str(tmp_path / "run"))
    assert events[0]["event"] == "run_start"
    assert events[-1]["event"] == "run_end"
    sp = spans_of(events)
    # inner finishes (and is therefore emitted) before outer
    assert [s["name"] for s in sp] == ["inner", "outer"]
    inner, outer = sp
    assert inner["depth"] == 1 and inner["parent"] == "outer"
    assert inner["tag"] == "a"
    assert outer["depth"] == 0 and "parent" not in outer
    assert outer["dur"] >= inner["dur"] >= 0.03
    # self time excludes the child exactly (three values each rounded to 1e-6
    # independently, so the identity holds to 1.5e-6 in the worst case)
    assert outer["self_dur"] == pytest.approx(
        outer["dur"] - inner["dur"], abs=2e-6
    )
    assert inner["self_dur"] == pytest.approx(inner["dur"], abs=1e-6)


def test_span_context_fields_attach_and_detach(tmp_path):
    obs.configure(str(tmp_path / "run"), run="t")
    obs.set_context(phase="xe", epoch=3, step=7)
    with obs.span("a"):
        pass
    obs.set_context(step=None)
    obs.event("ping")
    obs.shutdown()
    events = read_events(str(tmp_path / "run"))
    (a,) = spans_of(events, "a")
    assert (a["phase"], a["epoch"], a["step"]) == ("xe", 3, 7)
    (ping,) = [e for e in events if e["event"] == "ping"]
    assert ping["phase"] == "xe" and "step" not in ping


def test_span_attr_never_shadows_schema(tmp_path):
    obs.configure(str(tmp_path / "run"), run="t")
    with obs.span("ckpt.save", name="latest", dur="shadow"):
        pass
    obs.shutdown()
    (s,) = spans_of(read_events(str(tmp_path / "run")), "ckpt.save")
    assert s["name"] == "ckpt.save" and isinstance(s["dur"], float)
    assert s["attr_name"] == "latest" and s["attr_dur"] == "shadow"


def test_trace_json_is_perfetto_compatible(tmp_path):
    obs.configure(str(tmp_path / "run"), run="t")
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    w = obs.span("window", track="mytrack").begin()
    w.end()
    obs.shutdown()
    doc = json.load(open(tmp_path / "run" / "trace.json"))
    evs = doc["traceEvents"]
    assert {e["name"] for e in evs} == {"outer", "inner", "window"}
    for e in evs:
        assert e["ph"] == "X"
        assert set(e) >= {"name", "ph", "ts", "dur", "pid", "tid"}
        assert e["ts"] >= 0 and e["dur"] >= 0
    (win,) = [e for e in evs if e["name"] == "window"]
    assert win["tid"] == "mytrack"  # virtual track, not the thread


def test_disabled_mode_is_a_noop(tmp_path):
    """train.obs off: zero events, zero files, shared no-op span object."""
    assert obs.configure(str(tmp_path / "off"), enabled=False) is None
    assert not obs.enabled()
    s1, s2 = obs.span("a", big=1), obs.span("b")
    assert s1 is s2  # the shared singleton: no allocation per call
    with s1:
        pass
    obs.event("nope", x=1)
    obs.snapshot_metrics()
    obs.maybe_snapshot(100)
    assert not os.path.exists(tmp_path / "off")
    assert list(tmp_path.iterdir()) == []


def test_span_survives_foreign_stack_state(tmp_path):
    """A begin() left open (crash path) degrades accounting, never corrupts."""
    obs.configure(str(tmp_path / "run"), run="t")
    leaked = obs.span("leaked").begin()
    with obs.span("ok"):
        pass
    # ending the outer leaked span pops past the already-finished child
    leaked.end()
    obs.shutdown()
    names = [s["name"] for s in spans_of(read_events(str(tmp_path / "run")))]
    assert names == ["ok", "leaked"]


# ---- metrics ----------------------------------------------------------------

def test_histogram_bucket_math():
    h = Histogram("t", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 1.5, 3.0, 100.0):
        h.observe(v)
    assert h.counts == [1, 1, 1, 1]
    assert h.count == 4
    assert h.sum == pytest.approx(105.0)
    assert h.max == 100.0
    # boundary lands in the bucket it bounds (le semantics)
    h2 = Histogram("t2", buckets=(1.0, 2.0))
    h2.observe(1.0)
    assert h2.counts == [1, 0, 0]
    # interpolated quantiles: rank 2 of 4 tops out bucket (1, 2]
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantile(1.0) == 100.0  # overflow bucket reports the exact max
    assert h.quantile(0.0) == pytest.approx(0.5, abs=0.5)
    with pytest.raises(ValueError):
        h.quantile(1.5)
    with pytest.raises(ValueError):
        Histogram("bad", buckets=(2.0, 1.0))


def test_registry_kinds_and_conflicts():
    reg = Registry()
    c = reg.counter("a.b")
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    with pytest.raises(ValueError):
        c.inc(-1)
    reg.gauge("g").set(7)
    with pytest.raises(TypeError):
        reg.counter("g")  # name already registered as a gauge
    snap = reg.snapshot()
    assert snap["counters"]["a.b"] == 3.5
    assert snap["gauges"]["g"] == 7.0


def test_prometheus_textfile_format():
    reg = Registry()
    reg.counter("resilience.nan_skip").inc(3)
    reg.gauge("prefetch.queue_depth").set(2)
    h = reg.histogram("xe.step_seconds", buckets=(0.1, 0.5))
    for v in (0.05, 0.3, 2.0):
        h.observe(v)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# TYPE resilience_nan_skip counter" in lines
    assert "resilience_nan_skip 3" in lines
    assert "prefetch_queue_depth 2" in lines
    assert "# TYPE xe_step_seconds histogram" in lines
    # cumulative buckets + +Inf == count
    assert 'xe_step_seconds_bucket{le="0.1"} 1' in lines
    assert 'xe_step_seconds_bucket{le="0.5"} 2' in lines
    assert 'xe_step_seconds_bucket{le="+Inf"} 3' in lines
    assert "xe_step_seconds_count 3" in lines
    assert any(l.startswith("xe_step_seconds_sum 2.35") for l in lines)
    assert text.endswith("\n")


def test_step_meter_windows_and_compile_exclusion():
    meter = StepMeter("tmeter")
    meter.begin_epoch()
    meter.tick(8, first=True)   # compile step: excluded from the histogram
    time.sleep(0.01)
    meter.tick(8)
    meter.tick(8)
    s = meter.epoch_summary()
    assert s["steps"] == 2.0
    assert meter.clips.value == 16.0
    assert meter.compile_secs.value > 0.0
    assert meter.hist.count == 2
    assert s["clips_per_sec"] > 0.0
    # the next epoch windows its own deltas
    meter.begin_epoch()
    meter.tick(8)
    assert meter.epoch_summary()["steps"] == 1.0


def test_metrics_snapshot_lands_in_event_stream(tmp_path):
    obs.configure(str(tmp_path / "run"), run="t", snapshot_every=2)
    obs.counter("resilience.rollback").inc()
    obs.maybe_snapshot(1)   # off-cadence: no snapshot
    obs.maybe_snapshot(2)   # on-cadence
    obs.shutdown()          # final snapshot
    events = read_events(str(tmp_path / "run"))
    snaps = [e for e in events if e["event"] == "metrics"]
    assert len(snaps) == 2 and snaps[0]["step"] == 2
    assert snaps[-1]["final"] is True
    assert snaps[-1]["counters"]["resilience.rollback"] == 1
    # the Prometheus textfile is (re)written by snapshots
    prom = open(tmp_path / "run" / "metrics.prom").read()
    assert "resilience_rollback 1" in prom


# ---- profiler routing (satellite 1) -----------------------------------------

def test_step_profiler_routes_through_event_stream(tmp_path, monkeypatch):
    import jax

    from cst_captioning_tpu.utils.profiling import StepProfiler

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    obs.configure(str(tmp_path / "run"), run="t")
    logged = []
    prof = StepProfiler(str(tmp_path / "trace"), steps=2, skip=1,
                        log=lambda ev, **f: logged.append((ev, f)))
    for _ in range(5):
        prof.tick()
    assert calls == [("start", str(tmp_path / "trace")), ("stop",)]
    obs.shutdown()
    # no stderr print: completion is a structured event, to BOTH sinks
    assert logged == [(
        "profiler_trace_written",
        {"dir": str(tmp_path / "trace"), "steps": 2},
    )]
    events = read_events(str(tmp_path / "run"))
    assert [e for e in events if e["event"] == "profiler_trace_written"]
    # the capture window is a span on the profiler virtual track
    (win,) = spans_of(events, "profile.window")
    assert win["track"] == "profiler"


# ---- report -----------------------------------------------------------------

def test_report_aggregates_committed_fixture():
    rep = report_run(FIXTURE_RUN)
    assert rep["run"] == "fixture" and rep["complete"]
    assert rep["wall_s"] == pytest.approx(7.5)
    by_name = {p["phase"]: p for p in rep["phases"]}
    assert by_name["xe.step"]["count"] == 2
    assert by_name["xe.step"]["total_s"] == pytest.approx(0.9)
    assert by_name["xe.step"]["max_s"] == pytest.approx(0.5)
    # totals partition: covered == sum of self times, and the epoch spans
    # contribute only their input-wait self time
    assert rep["covered_s"] == pytest.approx(
        sum(p["self_s"] for p in rep["phases"])
    )
    assert by_name["xe.epoch"]["self_s"] == pytest.approx(1.1)
    assert rep["coverage"] == pytest.approx(6.4 / 7.5)
    # background work is reported but never summed against wall clock
    over = {p["phase"] for p in rep["overlap"]}
    assert over == {"prefetch.stage", "profile.window"}
    r = rep["resilience"]
    assert r["nan_skips"] == 1 and r["divergences"] == 2
    assert r["rollbacks"] == 1 and r["retry_attempts"] == 2
    assert r["ckpt_corrupt_fallbacks"] == 1
    assert r["chaos_faults"] == 3
    assert r["chaos_faults_by_kind"] == {"nan": 2, "io_error": 1}
    assert rep["compile"] == {"count": 4, "seconds": 2.5}
    text = render_report(rep)
    assert "xe.step" in text and "chaos faults injected: 3" in text
    assert "nan=2" in text and "rollbacks: 1" in text


def test_report_mfu_column_and_decode_section():
    """The phase table's mfu column (flops.<phase> counters over run wall x
    device.peak_flops, PR 4) and the decode early-exit section (depth
    histogram vs budget) — from a synthetic event stream."""
    span = lambda ts, name, dur: {  # noqa: E731
        "ts": ts, "event": "span", "name": name, "dur": dur,
        "self_dur": dur, "depth": 0, "thread": "main",
    }
    events = [
        {"ts": 0.0, "event": "run_start", "run": "mfu", "thread": "main"},
        span(1.0, "rl.decode", 4.0),
        span(6.0, "rl.update", 2.0),
        span(8.0, "xe.step", 1.0),
        {
            "ts": 9.0, "event": "metrics",
            "counters": {
                "flops.rl.decode": 4e12,   # / 10s wall / 1e12 peak = 0.4
                "flops.rl.update": 1e12,
                "flops.xe.step": 5e11,
            },
            "gauges": {"device.peak_flops": 1e12,
                       "rl.decode.budget": 30.0},
            "histograms": {
                "rl.decode.depth": {
                    "buckets": [10.0, 20.0, 30.0],
                    # two batches exited at depth 15, one ran the budget
                    "counts": [0, 2, 1, 0],
                    "sum": 60.0, "count": 3, "max": 30.0,
                },
            },
        },
        {"ts": 10.0, "event": "run_end", "run": "mfu"},
    ]
    rep = build_report(events)
    by_name = {p["phase"]: p for p in rep["phases"]}
    assert by_name["rl.decode"]["mfu"] == pytest.approx(0.4)
    assert by_name["rl.update"]["mfu"] == pytest.approx(0.1)
    assert by_name["xe.step"]["mfu"] == pytest.approx(0.05)
    d = rep["decode"]
    assert d["batches"] == 3 and d["budget"] == 30.0
    assert d["depth_mean"] == pytest.approx(20.0)
    assert d["saved_frac"] == pytest.approx(1.0 - 20.0 / 30.0)
    assert d["depth_max"] == 30.0
    text = render_report(rep)
    assert "mfu" in text and "0.4000" in text
    assert "decode early-exit" in text and "33.3% of the scan budget" in text


def test_report_mfu_blank_without_counters():
    """Rows without a flops counter (or with no peak gauge) get mfu=None and
    render blank — the fixture run predates the counters."""
    rep = report_run(FIXTURE_RUN)
    assert all(p["mfu"] is None for p in rep["phases"])
    assert rep["decode"] is None
    render_report(rep)  # renders without error


def test_scst_records_flops_and_depth():
    """An SCST step feeds the flops.rl.decode / flops.rl.update counters and
    (with a recorder installed) the rl.decode.depth histogram the report's
    MFU column and decode section read."""
    import jax
    import jax.numpy as jnp
    import numpy as _np

    from cst_captioning_tpu.config.config import (
        ModelConfig, RLConfig, TrainConfig,
    )
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl import SCSTTrainer
    from cst_captioning_tpu.train import create_train_state, make_optimizer

    obs.REGISTRY.reset()

    cfg = ModelConfig(
        vocab_size=20, modalities=(("resnet", 6),), d_embed=8, d_hidden=8,
        d_att=4, encoder="meanpool", dropout=0.0, max_len=5, max_frames=3,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = _np.random.default_rng(0)
    feats = {"resnet": jnp.asarray(rng.normal(size=(4, 3, 6)), jnp.float32)}
    masks = {"resnet": jnp.ones((4, 3), jnp.float32)}
    labels = jnp.asarray(rng.integers(4, 20, size=(4, 5)), jnp.int32)
    tx = make_optimizer(TrainConfig(lr=1e-3, grad_clip=5.0), 10)
    state = create_train_state(model, tx, (feats, masks, labels), seed=1)

    reward = lambda vids, rows: _np.ones(len(rows), _np.float32)  # noqa: E731
    scst = SCSTTrainer(
        model, reward, RLConfig(enabled=True, num_rollouts=2, baseline="greedy")
    )
    state, _ = scst.train_step(
        state, feats, masks, ["v0", "v1", "v2", "v3"], jax.random.key(0)
    )
    snap = obs.snapshot()
    assert snap["counters"]["flops.rl.decode"] > 0
    assert snap["counters"]["flops.rl.update"] > 0
    assert snap["gauges"]["rl.decode.budget"] == 5.0
    # the depth histogram only records when a recorder is installed
    assert "rl.decode.depth" not in snap["histograms"]
    obs.REGISTRY.reset()


def test_report_handles_torn_stream_and_missing_end(tmp_path):
    d = tmp_path / "run"
    d.mkdir()
    lines = [
        json.dumps({"ts": 10.0, "event": "run_start", "run": "torn",
                    "thread": "MainThread"}),
        json.dumps({"ts": 11.0, "event": "span", "name": "xe.step",
                    "dur": 1.0, "self_dur": 1.0, "depth": 0,
                    "thread": "MainThread"}),
        '{"ts": 12.0, "event": "span", "na',  # torn final line (kill -9)
    ]
    (d / "events.jsonl").write_text("\n".join(lines))
    # build_report over hand-parsed events == report_run over the torn file
    assert build_report([json.loads(l) for l in lines[:2]])["wall_s"] == 1.0
    rep = report_run(str(d))
    assert not rep["complete"]
    assert rep["wall_s"] == pytest.approx(1.0)  # first..last parseable ts
    assert rep["phases"][0]["phase"] == "xe.step"
    assert "did not close cleanly" in render_report(rep)


def test_report_missing_dir_errors_cleanly(tmp_path):
    from cst_captioning_tpu.cli.obs_report import main as report_main

    assert report_main([str(tmp_path / "nope")]) == 2
    with pytest.raises(FileNotFoundError):
        report_run(str(tmp_path / "nope"))


def test_obs_report_cli_json(tmp_path, capsys):
    from cst_captioning_tpu.cli.obs_report import main as report_main

    assert report_main([FIXTURE_RUN, "--json"]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["run"] == "fixture"
    assert {p["phase"] for p in rep["phases"]} >= {"xe.step", "rl.reward"}
    capsys.readouterr()
    assert report_main([FIXTURE_RUN]) == 0
    assert "resilience:" in capsys.readouterr().out


def test_live_roundtrip_report_covers_wall_clock(tmp_path):
    """Recorder -> stream -> report: coverage ~1 for fully spanned runs."""
    obs.configure(str(tmp_path / "run"), run="t")
    with obs.span("xe.epoch"):
        for _ in range(3):
            with obs.span("xe.step"):
                time.sleep(0.01)
    with obs.span("eval"):
        time.sleep(0.02)
    obs.shutdown()
    rep = report_run(str(tmp_path / "run"))
    assert rep["complete"]
    by_name = {p["phase"]: p for p in rep["phases"]}
    assert by_name["xe.step"]["count"] == 3
    # phase totals sum to (nearly) the measured wall clock
    assert rep["coverage"] > 0.9
    assert rep["covered_s"] <= rep["wall_s"] + 1e-6


# ---- chaos-run report (satellite: injected faults are visible) --------------

@pytest.fixture(scope="module")
def chaos_datasets(tmp_path_factory):
    from cst_captioning_tpu.data import CaptionDataset, make_synthetic_dataset

    out = tmp_path_factory.mktemp("obssynth")
    synth = make_synthetic_dataset(
        str(out), num_videos=12, num_topics=3, vocab_words=20,
        modalities={"resnet": 16}, max_frames=4, seed=5,
    )
    train = CaptionDataset(
        synth["info_json"], {"resnet": synth["resnet"]}, "train", 4
    )
    return train


def test_chaos_run_report_shows_injected_faults(chaos_datasets, tmp_path):
    from cst_captioning_tpu.config.config import (
        DataConfig,
        EvalConfig,
        ExperimentConfig,
        ModelConfig,
        RLConfig,
        TrainConfig,
    )
    from cst_captioning_tpu.resilience import Fault, FaultPlan
    from cst_captioning_tpu.train.trainer import Trainer

    train_ds = chaos_datasets
    ckpt = str(tmp_path / "ckpt")
    run_dir = str(tmp_path / "obs")
    cfg = ExperimentConfig(
        name="obs-chaos",
        model=ModelConfig(
            vocab_size=len(train_ds.vocab), modalities=(("resnet", 16),),
            d_embed=16, d_hidden=16, d_att=8, encoder="temporal_attention",
            dropout=0.0, max_len=8, max_frames=4, dtype="float32",
        ),
        data=DataConfig(batch_size=8, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, grad_clip=5.0, ckpt_dir=ckpt, seed=0, epochs=1,
            eval_every_epochs=100, log_every_steps=1,
            obs=True, obs_dir=run_dir,
        ),
        rl=RLConfig(enabled=False),
        eval=EvalConfig(beam_size=1, max_len=8),
    )
    tr = Trainer(cfg, train_ds, None, log_path=ckpt + "/ev.jsonl",
                 use_mesh=False)
    plan = FaultPlan([Fault("xe.batch", "nan", at=1)])
    with plan.activate():
        tr.train_xe()
    obs.shutdown()
    assert plan.fired

    rep = report_run(run_dir)
    by_name = {p["phase"]: p for p in rep["phases"]}
    # the instrumented run produced the phase table...
    assert by_name["xe.step"]["count"] == 3
    assert "setup" in by_name and "ckpt.save" in by_name
    assert rep["coverage"] > 0.5
    # ...and the resilience summary shows the injected fault end to end:
    # chaos activation -> device guard nan-skip -> sentinel verdict
    r = rep["resilience"]
    assert r["chaos_faults"] >= 1
    assert r["chaos_faults_by_kind"].get("nan", 0) >= 1
    assert r["nan_skips"] == 1
    assert r["divergences"] == 1
    text = render_report(rep)
    assert "nan-skips: 1" in text


def test_trainer_epoch_events_report_meter_latency(chaos_datasets, tmp_path):
    """Satellite: XE epochs log obs-histogram latency (the StepTimer
    replacement) — identical field names to the RL epoch summary."""
    from cst_captioning_tpu.config.config import (
        DataConfig,
        EvalConfig,
        ExperimentConfig,
        ModelConfig,
        RLConfig,
        TrainConfig,
    )
    from cst_captioning_tpu.train.trainer import Trainer

    train_ds = chaos_datasets
    ckpt = str(tmp_path / "ckpt")
    cfg = ExperimentConfig(
        name="meter",
        model=ModelConfig(
            vocab_size=len(train_ds.vocab), modalities=(("resnet", 16),),
            d_embed=16, d_hidden=16, d_att=8, encoder="meanpool",
            dropout=0.0, max_len=8, max_frames=4, dtype="float32",
        ),
        data=DataConfig(batch_size=8, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, ckpt_dir=ckpt, seed=0, epochs=1, eval_every_epochs=100,
        ),
        rl=RLConfig(enabled=True, num_rollouts=2, lr=1e-3, epochs=1,
                    baseline="greedy", pipelined=False),
        eval=EvalConfig(beam_size=1, max_len=8),
    )
    tr = Trainer(cfg, train_ds, None, log_path=ckpt + "/ev.jsonl",
                 use_mesh=False)
    tr.train_xe()
    tr.train_rl()
    events = [json.loads(l) for l in open(ckpt + "/ev.jsonl")]
    (xe,) = [e for e in events if e["event"] == "xe_epoch"]
    (rl,) = [e for e in events if e["event"] == "rl_epoch"]
    keys = {"steps", "clips_per_sec", "step_seconds_p50", "step_seconds_p95"}
    assert keys <= set(xe) and keys <= set(rl)
    assert xe["steps"] == 3.0 - 1.0  # first (compile) step excluded
    assert xe["clips_per_sec"] > 0 and rl["clips_per_sec"] > 0
    assert np.isfinite(xe["step_seconds_p95"])


def test_report_decode_compaction_counters():
    """The decode section surfaces the rl.decode.compaction counter pair
    (lanes stepped vs compacted away) and the renderer prints the ledger."""
    events = [
        {"ts": 0.0, "event": "run_start", "run": "comp", "thread": "main"},
        {
            "ts": 1.0, "event": "metrics",
            "counters": {
                "rl.decode.compaction.lanes_stepped": 300.0,
                "rl.decode.compaction.lanes_skipped": 100.0,
            },
            "gauges": {"rl.decode.budget": 30.0},
            "histograms": {
                "rl.decode.depth": {
                    "buckets": [10.0, 20.0, 30.0],
                    "counts": [0, 1, 0, 0],
                    "sum": 15.0, "count": 1, "max": 15.0,
                },
            },
        },
        {"ts": 2.0, "event": "run_end", "run": "comp"},
    ]
    rep = build_report(events)
    d = rep["decode"]
    assert d["lanes_stepped"] == 300.0 and d["lanes_skipped"] == 100.0
    assert d["compaction_saved_frac"] == pytest.approx(0.25)
    text = render_report(rep)
    assert "decode compaction" in text and "25.0% of lane-steps" in text
    assert rep["update"] is None and "update row blocks" not in text


def test_report_update_row_blocks():
    """The ``rl.update.row_blocks`` / ``rl.update.block_rows`` gauges
    (rl/scst.py sets them when the update is traced) reach the report and
    its text, beside the decode early-exit section or without one."""
    metrics = lambda gauges, **kw: {  # noqa: E731
        "ts": 1.0, "event": "metrics", "counters": {}, "gauges": gauges,
        "histograms": {}, **kw,
    }
    blocks = {"rl.update.row_blocks": 4.0, "rl.update.block_rows": 448.0}
    events = [
        {"ts": 0.0, "event": "run_start", "run": "blocks", "thread": "main"},
        metrics(blocks),
        {"ts": 2.0, "event": "run_end", "run": "blocks"},
    ]
    rep = build_report(events)
    assert rep["update"] == {"row_blocks": 4.0, "block_rows": 448.0}
    assert rep["decode"] is None
    text = render_report(rep)
    assert "update row blocks: 4 block(s) of 448 row(s)" in text
    depth = {"rl.decode.depth": {"buckets": [10.0, 20.0, 30.0],
                                 "counts": [0, 1, 0, 0],
                                 "sum": 15.0, "count": 1, "max": 15.0}}
    events[1] = metrics({**blocks, "rl.decode.budget": 30.0},
                        histograms=depth)
    lines = render_report(build_report(events)).splitlines()
    at = next(i for i, ln in enumerate(lines) if "decode early-exit" in ln)
    assert lines[at + 1].startswith("update row blocks: 4 block(s)")
    assert "update word embedding" not in "\n".join(lines)


def test_report_update_embed_rows():
    """The ``rl.update.embed_rows`` gauge (rl/scst.py sets it when the
    update's teacher forcing is traced: positions x rows of what one call
    looks up before its forward loop and sums into the word embedding after
    its backward loop) reaches the report and its text beside the row
    blocks' line; tracing the update sets it."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import ModelConfig
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl import scst

    gauges = {"rl.update.row_blocks": 4.0, "rl.update.block_rows": 448.0,
              "rl.update.embed_rows": 13440.0}
    rep = build_report([
        {"ts": 0.0, "event": "run_start", "run": "rows", "thread": "main"},
        {"ts": 1.0, "event": "metrics", "counters": {}, "gauges": gauges,
         "histograms": {}},
        {"ts": 2.0, "event": "run_end", "run": "rows"},
    ])
    assert rep["update"]["embed_rows"] == 13440.0
    lines = render_report(rep).splitlines()
    at = next(i for i, ln in enumerate(lines)
              if ln.startswith("update row blocks:"))
    assert lines[at + 1].startswith(
        "update word embedding: 13440 input row(s) a block")

    # the program's side: 2 chunks of K = 4 over B = 6 rows under a cap of
    # 8 make blocks of 3 rows x 2 rollouts, T = 4 positions each
    cfg = ModelConfig(vocab_size=11, modalities=(("resnet", 8),), d_embed=8,
                      d_hidden=8, d_att=4, max_len=4, max_frames=3,
                      dtype="float32")
    model = CaptionModel(cfg)
    feats = {"resnet": jnp.zeros((6, 3, 8), jnp.float32)}
    masks = {"resnet": jnp.ones((6, 3), jnp.float32)}
    params = model.init(jax.random.key(0), feats, masks,
                        jnp.zeros((6, 4), jnp.int32))
    obs.gauge("rl.update.embed_rows").set(0.0)
    patch = pytest.MonkeyPatch()
    patch.setattr(scst, "_ROW_BLOCK_CAP", 8)
    try:
        jax.eval_shape(
            lambda p: scst._chunked_loss_grads(
                model, p, feats, masks, jnp.ones((4, 6, 4), jnp.int32),
                jnp.ones((4, 6)), jnp.ones((6,)), chunks=2),
            params)
    finally:
        patch.undo()
    assert obs.gauge("rl.update.block_rows").value == 3.0
    assert obs.gauge("rl.update.embed_rows").value == 4 * 3 * 2


def test_report_decode_sampler_draws():
    """The ``rl.decode.sampler_draws`` gauge (decoding/sample.py sets it
    when ``sample_decode`` is traced: K x B, one uniform a lane) reaches the
    report's decode section and its text; a run that traced no
    ``sample_decode`` shows no such line."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import ModelConfig
    from cst_captioning_tpu.decoding import sample_decode
    from cst_captioning_tpu.models import CaptionModel

    depth = {"rl.decode.depth": {"buckets": [10.0, 20.0, 30.0],
                                 "counts": [0, 1, 0, 0],
                                 "sum": 15.0, "count": 1, "max": 15.0}}
    events = lambda gauges: [  # noqa: E731
        {"ts": 0.0, "event": "run_start", "run": "draws", "thread": "main"},
        {"ts": 1.0, "event": "metrics", "counters": {}, "gauges": gauges,
         "histograms": depth},
        {"ts": 2.0, "event": "run_end", "run": "draws"},
    ]
    rep = build_report(events({"rl.decode.budget": 30.0,
                               "rl.decode.sampler_draws": 8960.0}))
    assert rep["decode"]["sampler_draws"] == 8960.0
    assert "decode sampler: 8960 random draw(s) a step" in render_report(rep)
    rep = build_report(events({"rl.decode.budget": 30.0}))
    assert rep["decode"]["sampler_draws"] == 0.0
    assert "decode sampler" not in render_report(rep)

    # the program's side: tracing sample_decode sets K x B
    cfg = ModelConfig(vocab_size=11, modalities=(("resnet", 8),), d_embed=8,
                      d_hidden=8, d_att=4, max_len=4, max_frames=3,
                      dtype="float32")
    model = CaptionModel(cfg)
    feats = {"resnet": jnp.zeros((6, 3, 8), jnp.float32)}
    masks = {"resnet": jnp.ones((6, 3), jnp.float32)}
    params = model.init(jax.random.key(0), feats, masks,
                        jnp.zeros((6, 4), jnp.int32))
    obs.gauge("rl.decode.sampler_draws").set(0.0)
    jax.eval_shape(lambda p: sample_decode(
        model, p, feats, masks, jax.random.key(1), num_rollouts=5), params)
    assert obs.gauge("rl.decode.sampler_draws").value == 30.0


def test_scst_records_compaction_counters(tmp_path):
    """With a recorder installed, an SCST step feeds the depth histogram
    AND the compaction counter pair from the decoded tokens (the default
    decode compacts, so both counters exist and sum to G*B*depth)."""
    import jax
    import jax.numpy as jnp
    import numpy as _np

    from cst_captioning_tpu.config.config import (
        ModelConfig, RLConfig, TrainConfig,
    )
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl import SCSTTrainer
    from cst_captioning_tpu.train import create_train_state, make_optimizer

    obs.REGISTRY.reset()
    obs.configure(str(tmp_path / "obs"), run="comp")
    try:
        cfg = ModelConfig(
            vocab_size=20, modalities=(("resnet", 6),), d_embed=8,
            d_hidden=8, d_att=4, encoder="meanpool", dropout=0.0, max_len=6,
            max_frames=3, dtype="float32", decode_stride=2,
        )
        model = CaptionModel(cfg)
        rng = _np.random.default_rng(0)
        feats = {
            "resnet": jnp.asarray(rng.normal(size=(4, 3, 6)), jnp.float32)
        }
        masks = {"resnet": jnp.ones((4, 3), jnp.float32)}
        labels = jnp.asarray(rng.integers(4, 20, size=(4, 6)), jnp.int32)
        tx = make_optimizer(TrainConfig(lr=1e-3, grad_clip=5.0), 10)
        state = create_train_state(model, tx, (feats, masks, labels), seed=1)
        reward = lambda vids, rows: _np.ones(  # noqa: E731
            len(rows), _np.float32
        )
        scst = SCSTTrainer(
            model, reward,
            RLConfig(enabled=True, num_rollouts=2, baseline="greedy"),
        )
        state, _ = scst.train_step(
            state, feats, masks, ["v0", "v1", "v2", "v3"], jax.random.key(0)
        )
        snap = obs.snapshot()
        stepped = snap["counters"]["rl.decode.compaction.lanes_stepped"]
        skipped = snap["counters"]["rl.decode.compaction.lanes_skipped"]
        depth = snap["histograms"]["rl.decode.depth"]["sum"]
        assert stepped > 0 and skipped >= 0
        assert stepped + skipped == 3 * 4 * depth  # G * B * depth
    finally:
        obs.shutdown()
        obs.REGISTRY.reset()


def test_observe_device_memory_samples_all_local_devices(monkeypatch):
    """Every local device lands in device<k>.* gauges; the legacy aggregate
    device.* gauges carry the max (the HBM-headroom signal on a balanced
    mesh; ROADMAP obs open item, closed PR 5)."""
    import jax

    from cst_captioning_tpu.obs import metrics as m

    class FakeDev:
        def __init__(self, i, used, peak):
            self.id = i
            self._s = {"bytes_in_use": used, "peak_bytes_in_use": peak,
                       "bytes_limit": 100.0}

        def memory_stats(self):
            return self._s

    reg = m.Registry()
    monkeypatch.setattr(
        jax, "local_devices", lambda: [FakeDev(0, 10.0, 30.0),
                                       FakeDev(1, 20.0, 25.0)]
    )
    assert m.observe_device_memory(reg) is True
    snap = reg.snapshot()["gauges"]
    assert snap["device0.bytes_in_use"] == 10.0
    assert snap["device1.bytes_in_use"] == 20.0
    assert snap["device.bytes_in_use"] == 20.0        # max across devices
    assert snap["device.peak_bytes_in_use"] == 30.0   # device 0's peak
    assert snap["device1.peak_bytes_in_use"] == 25.0


def test_observe_device_memory_statless_backend(monkeypatch):
    """CPU-style backends (memory_stats() -> None) write nothing."""
    import jax

    from cst_captioning_tpu.obs import metrics as m

    class NoStats:
        id = 0

        def memory_stats(self):
            return None

    reg = m.Registry()
    monkeypatch.setattr(jax, "local_devices", lambda: [NoStats()])
    assert m.observe_device_memory(reg) is False
    assert reg.snapshot()["gauges"] == {}


# ---- multihost report merge + health/DCN sections ---------------------------

def _write_stream(path, events):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")


def _proc_events(t0, t1, phase_self, counters=None, gauges=None,
                 histograms=None):
    return [
        {"ts": t0, "event": "run_start", "run": "multi", "thread": "MainThread"},
        {"ts": t0 + 0.1, "event": "span", "name": "rl.decode",
         "dur": phase_self, "self_dur": phase_self, "thread": "MainThread"},
        {"ts": t1 - 0.01, "event": "metrics",
         "counters": counters or {}, "gauges": gauges or {},
         "histograms": histograms or {}},
        {"ts": t1, "event": "run_end"},
    ]


def test_report_merges_proc_streams_with_skew_attribution(tmp_path):
    """proc<k>/ sub-streams merge into hosts/cluster sections: per-host
    start/end skew names the straggler, counters sum cluster-wide."""
    run = str(tmp_path / "run")
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(100.0, 110.0, 5.0,
                     counters={"resilience.chaos_fault": 1,
                               "health.dcn_stall": 1,
                               "health.heartbeats": 7}),
    )
    _write_stream(
        os.path.join(run, "proc1", "events.jsonl"),
        _proc_events(100.5, 113.0, 9.0,
                     counters={"resilience.chaos_fault": 2,
                               "health.peer_lost": 1,
                               "health.heartbeats": 5}),
    )
    rep = report_run(run)
    assert [h["proc"] for h in rep["hosts"]] == [0, 1]
    h0, h1 = rep["hosts"]
    assert h0["start_skew_s"] == pytest.approx(0.0)
    assert h1["start_skew_s"] == pytest.approx(0.5)
    assert h0["end_skew_s"] == pytest.approx(0.0)
    assert h1["end_skew_s"] == pytest.approx(3.0)
    assert h1["top_phase"] == "rl.decode"
    c = rep["cluster"]
    assert c["processes"] == 2 and c["straggler_proc"] == 1
    assert c["max_end_skew_s"] == pytest.approx(3.0)
    assert c["chaos_faults"] == 3
    assert c["dcn_stalls"] == 1 and c["peer_losses"] == 1
    assert c["heartbeats"] == 12
    # rendering includes the cluster table without touching the phase table
    text = render_report(rep)
    assert "cluster: 2 process streams merged" in text
    assert "proc" in text


def test_report_single_stream_has_no_cluster_section(tmp_path):
    run = str(tmp_path / "run")
    _write_stream(os.path.join(run, "events.jsonl"),
                  _proc_events(0.0, 1.0, 0.5))
    rep = report_run(run)
    assert "hosts" not in rep and "cluster" not in rep


def test_report_health_section_surfaces_heartbeats_and_dcn_stalls(tmp_path):
    run = str(tmp_path / "run")
    hist = {"dcn.collective_seconds": {
        "buckets": [0.1, 1.0], "counts": [8, 2], "sum": 2.4, "count": 10,
        "max": 0.9,
    }}
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(0.0, 10.0, 1.0,
                     counters={"health.heartbeats": 20,
                               "health.dcn_stall": 2,
                               "health.peer_lost": 1,
                               "resilience.peer_loss_drain": 1,
                               "resilience.degraded_continuation": 1,
                               "resilience.ckpt_enospc": 3,
                               "resilience.prefetch_stall": 4},
                     gauges={"health.peers_alive": 1.0,
                             "health.peer_age_max_s": 0.2},
                     histograms=hist),
    )
    rep = report_run(run)
    h = rep["health"]
    assert h["heartbeats"] == 20 and h["dcn_stalls"] == 2
    assert h["peer_losses"] == 1 and h["peers_alive"] == 1.0
    assert h["collectives"] == 10
    assert 0.0 < h["collective_p95_s"] <= 0.9
    r = rep["resilience"]
    assert r["peer_loss_drains"] == 1
    assert r["degraded_continuations"] == 1
    assert r["ckpt_enospc"] == 3 and r["prefetch_stalls"] == 4
    text = render_report(rep)
    assert "health: 20 heartbeat(s)" in text
    assert "2 stall(s)" in text
    assert "peer-loss drains: 1" in text and "degraded continuations: 1" in text


def test_report_no_health_section_without_signals(tmp_path):
    run = str(tmp_path / "run")
    _write_stream(os.path.join(run, "events.jsonl"),
                  _proc_events(0.0, 1.0, 0.5))
    rep = report_run(run)
    assert rep["health"] is None
    assert "health:" not in render_report(rep)


# ---- XLA HLO cost-analysis backend (obs/flops.compiled_cost) ----------------


def test_compiled_cost_reports_hlo_flops():
    """The compiled-program FLOPs backend: a known matmul's HLO cost is
    exactly 2*m*n*k, and jitted callables are accepted as-is."""
    import jax
    import numpy as np

    from cst_captioning_tpu.obs.flops import compiled_cost

    a = np.ones((32, 48), np.float32)
    b = np.ones((48, 16), np.float32)
    cost = compiled_cost(lambda x, y: x @ y, a, b)
    assert cost is not None
    assert cost["flops"] == 2 * 32 * 48 * 16
    assert cost["bytes_accessed"] > 0
    jitted = jax.jit(lambda x, y: x @ y)
    cost2 = compiled_cost(jitted, a, b)
    assert cost2 is not None and cost2["flops"] == cost["flops"]


def test_compiled_cost_degrades_to_none():
    """Analysis failures degrade to None (the analytic-model fallback), by
    contract — never to a crash."""
    from cst_captioning_tpu.obs.flops import compiled_cost

    # not traceable -> lower() raises inside -> None
    assert compiled_cost(lambda: open("/nonexistent")) is None


def test_report_serving_section_from_synthetic_events(tmp_path):
    """The serving section aggregates the engine's funnel counters + the
    per-request phase histograms (queue-wait / encode / decode / detok)."""
    import os

    from cst_captioning_tpu.obs.report import render_report, report_run

    run = str(tmp_path / "run")
    hist = {}
    for name, p50 in (("queue_wait", 0.01), ("encode", 0.02),
                      ("decode", 0.3), ("detok", 0.001), ("latency", 0.35)):
        hist[f"serving.{name}_seconds"] = {
            "buckets": [0.001, 0.01, 0.1, 1.0],
            "counts": [0, 0, 5, 0], "sum": 5 * p50, "count": 5, "max": p50,
        }
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(0.0, 2.0, 0.5,
                     counters={"serving.requests_submitted": 6,
                               "serving.requests_admitted": 5,
                               "serving.requests_completed": 5,
                               "serving.strides": 9,
                               "serving.drains": 1,
                               "serving.admission_blocked_pages": 2},
                     gauges={"serving.pages_in_use": 3.0},
                     histograms=hist),
    )
    rep = report_run(run)
    sv = rep["serving"]
    assert sv["submitted"] == 6 and sv["completed"] == 5
    assert sv["strides"] == 9 and sv["drains"] == 1
    assert sv["admission_blocked_pages"] == 2
    assert set(sv["phases"]) == {"queue_wait", "encode", "decode", "detok"}
    assert sv["phases"]["decode"]["count"] == 5
    assert sv["latency_p95_s"] > 0
    text = render_report(rep)
    assert "serving: 6 submitted, 5 admitted, 5 completed" in text
    assert "queue_wait" in text and "page backpressure" in text


def test_report_serving_paged_bank_section(tmp_path):
    """The serving section surfaces the paged in-kernel attention
    telemetry: page-table occupancy gauges, encode-ahead staging depth,
    and the HBM bytes the killed dense-bank gather would have moved."""
    import os

    from cst_captioning_tpu.obs.report import render_report, report_run

    run = str(tmp_path / "run")
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(0.0, 2.0, 0.5,
                     counters={"serving.requests_submitted": 8,
                               "serving.requests_admitted": 8,
                               "serving.requests_completed": 8,
                               "serving.requests_staged": 3,
                               "serving.strides": 12,
                               "serving.gather_bytes_avoided": 6 * 2**20},
                     gauges={"serving.pages.in_use": 10.0,
                             "serving.pages.free": 2.0,
                             "serving.pages.table_rows": 4.0}),
    )
    rep = report_run(run)
    sv = rep["serving"]
    assert sv["pages"] == {"in_use": 10.0, "free": 2.0, "table_rows": 4.0}
    assert sv["staged"] == 3
    assert sv["gather_bytes_avoided"] == 6 * 2**20
    text = render_report(rep)
    assert "page table: 10 in use / 2 free over 4 row(s)" in text
    assert "staged admissions: 3" in text
    assert "gather bytes avoided: 6.0 MiB" in text


def test_report_no_serving_section_without_requests(tmp_path):
    import os

    from cst_captioning_tpu.obs.report import render_report, report_run

    run = str(tmp_path / "run")
    _write_stream(os.path.join(run, "events.jsonl"),
                  _proc_events(0.0, 1.0, 0.5))
    rep = report_run(run)
    assert rep["serving"] is None
    assert "serving:" not in render_report(rep)


# ---- Obs v2: prometheus specials, FLOPs-backend tags, serving SLO -----------


def test_prometheus_nonfinite_and_cumulative_inf_bucket():
    """_prom_num pins: gauges/counters holding NaN/±Inf render the Prometheus
    spellings ("NaN"/"+Inf"/"-Inf" — repr would emit "nan" and break
    scrapers), and the histogram's +Inf cumulative bucket always equals the
    total count even when every observation overflows the bounds."""
    reg = Registry()
    reg.gauge("loss.last").set(float("nan"))
    reg.gauge("burn.fast").set(float("inf"))
    reg.gauge("burn.neg").set(float("-inf"))
    reg.counter("secs").inc(1.5)
    h = reg.histogram("lat", buckets=(0.1,))
    h.observe(5.0)
    h.observe(7.0)  # both overflow: finite buckets stay 0
    lines = reg.to_prometheus().splitlines()
    assert "loss_last NaN" in lines
    assert "burn_fast +Inf" in lines
    assert "burn_neg -Inf" in lines
    assert "secs 1.5" in lines
    assert 'lat_bucket{le="0.1"} 0' in lines
    assert 'lat_bucket{le="+Inf"} 2' in lines
    assert "lat_count 2" in lines


def test_report_mfu_rows_carry_flops_backend(tmp_path):
    """Obs v2 satellite: each phase row labels WHICH FLOPs source its mfu
    reflects (compiled XLA cost vs the analytic model) — in the JSON field
    and as the c/a mark + legend in the rendered table."""
    run = str(tmp_path / "run")
    events = [
        {"ts": 0.0, "event": "run_start", "run": "b", "thread": "MainThread"},
        {"ts": 1.0, "event": "span", "name": "xe.step", "dur": 1.0,
         "self_dur": 1.0, "thread": "MainThread"},
        {"ts": 3.0, "event": "span", "name": "rl.update", "dur": 1.0,
         "self_dur": 1.0, "thread": "MainThread"},
        {"ts": 5.0, "event": "span", "name": "rl.decode", "dur": 1.0,
         "self_dur": 1.0, "thread": "MainThread"},
        {"ts": 9.9, "event": "metrics",
         "counters": {"flops.xe.step": 1e12, "flops.rl.update": 2e12,
                      "flops.rl.decode": 3e12},
         "gauges": {"device.peak_flops": 1e12,
                    "flops.backend.xe.step": 1.0,     # compiled probe hit
                    "flops.backend.rl.update": 0.0}}, # analytic fallback
        {"ts": 10.0, "event": "run_end"},
    ]
    _write_stream(os.path.join(run, "events.jsonl"), events)
    rep = report_run(run)
    by_name = {p["phase"]: p for p in rep["phases"]}
    assert by_name["xe.step"]["flops_backend"] == "compiled"
    assert by_name["rl.update"]["flops_backend"] == "analytic"
    assert by_name["rl.decode"]["flops_backend"] is None  # no gauge: untagged
    text = render_report(rep)
    assert "0.1000c" in text and "0.2000a" in text
    assert "mfu flops source: c = compiled program" in text


def test_report_serving_slo_section(tmp_path):
    """The serving section surfaces the SLO monitor's per-window attainment/
    burn-rate gauges, breach + alert counters, and the target."""
    run = str(tmp_path / "run")
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(0.0, 2.0, 0.5,
                     counters={"serving.requests_submitted": 10,
                               "serving.requests_admitted": 10,
                               "serving.requests_completed": 10,
                               "serving.strides": 4,
                               "serving.slo.breaches": 3,
                               "serving.slo.alerts": 1},
                     gauges={"serving.slo.target_s": 0.25,
                             "serving.slo.attainment.60s": 0.7,
                             "serving.slo.burn_rate.60s": 30.0,
                             "serving.slo.attainment.600s": 0.97,
                             "serving.slo.burn_rate.600s": 3.0}),
    )
    rep = report_run(run)
    slo = rep["serving"]["slo"]
    assert slo["target_s"] == 0.25
    assert slo["windows"][60]["attainment"] == pytest.approx(0.7)
    assert slo["windows"][60]["burn_rate"] == pytest.approx(30.0)
    assert slo["windows"][600]["burn_rate"] == pytest.approx(3.0)
    assert slo["breaches"] == 3 and slo["alerts"] == 1
    text = render_report(rep)
    assert "slo (target 0.250s):" in text
    assert "60s: 70.0% (burn 30.0x)" in text
    assert "breaches: 3" in text and "alerts: 1" in text


def test_report_serving_without_slo_has_no_slo_key(tmp_path):
    run = str(tmp_path / "run")
    _write_stream(
        os.path.join(run, "events.jsonl"),
        _proc_events(0.0, 1.0, 0.5,
                     counters={"serving.requests_submitted": 1,
                               "serving.requests_admitted": 1,
                               "serving.requests_completed": 1}),
    )
    rep = report_run(run)
    assert "slo" not in rep["serving"]
    assert "slo" not in render_report(rep)


# ---- spans where the host's time goes (prefetch, collate, RL epoch) ----------

_INPUT_SPANS = {"prefetch.stage", "data.epoch_order", "data.collate",
                "prefetch.h2d", "prefetch.wait"}
_RL_SPANS = {"rl.epoch.keys", "rl.epoch.drain", "ckpt.readback",
             "rl.reward.readback", "rl.reward.observe", "rl.reward.score"}


def _spy_on_spans(monkeypatch):
    """Every ``obs.span(...)`` call site reached: name -> the objects it got."""
    seen: dict[str, list] = {}
    real = obs.span

    def spy(name, /, **kw):
        s = real(name, **kw)
        seen.setdefault(name, []).append(s)
        return s

    monkeypatch.setattr(obs, "span", spy)
    return seen


def _prefetch_an_epoch(train_ds, size):
    from cst_captioning_tpu.data.batcher import Batcher
    from cst_captioning_tpu.data.prefetch import prefetch_to_device

    batcher = Batcher(train_ds, batch_size=4, max_len=8, mode="video")
    got = list(prefetch_to_device(
        batcher.epoch(), size=size,
        transform=lambda b: (b.feats, b.feat_masks),
    ))
    assert len(got) == batcher.num_batches() > 1
    return len(got)


def _inside(child, parent, slack_us=1.0):
    return (child["tid"] == parent["tid"]
            and child["ts"] >= parent["ts"] - slack_us
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + slack_us)


@pytest.mark.parametrize("size", [2, 0], ids=["worker", "inline"])
def test_prefetch_spans_cover_pull_collate_and_upload(
    chaos_datasets, tmp_path, size,
):
    """One prefetch.stage a batch on the staging thread, from before the
    pull (the collate runs in it) to after the upload; none for the pull
    that finds the epoch at its end; one prefetch.wait per get on the
    consumer's thread, the get of the end marker included."""
    import threading

    obs.configure(str(tmp_path / "run"), run="t")
    n = _prefetch_an_epoch(chaos_datasets, size)
    obs.shutdown()
    evs = json.load(open(tmp_path / "run" / "trace.json"))["traceEvents"]
    by = {name: [e for e in evs if e["name"] == name] for name in _INPUT_SPANS}
    me = threading.current_thread().name
    stager = "prefetch" if size else me
    assert len(by["prefetch.stage"]) == n
    assert {e["tid"] for e in by["prefetch.stage"]} == {stager}
    assert len(by["data.epoch_order"]) == 1
    for name in ("data.collate", "prefetch.h2d"):
        assert len(by[name]) == n
        for stage in by["prefetch.stage"]:
            assert sum(_inside(e, stage) for e in by[name]) == 1, name
    assert _inside(by["data.epoch_order"][0], by["prefetch.stage"][0])
    assert [e["args"]["rows"] for e in by["data.collate"]] == [4] * n
    # the inline path has no queue to wait on
    assert len(by["prefetch.wait"]) == (n + 1 if size else 0)
    assert {e["tid"] for e in by["prefetch.wait"]} <= {me}
    parents = {s["name"]: s.get("parent")
               for s in spans_of(read_events(str(tmp_path / "run")))}
    assert parents["data.collate"] == parents["prefetch.h2d"] == "prefetch.stage"


def test_input_span_call_sites_are_noops_when_disabled(
    chaos_datasets, tmp_path, monkeypatch,
):
    from cst_captioning_tpu.obs.span import _NOOP

    monkeypatch.chdir(tmp_path)
    seen = _spy_on_spans(monkeypatch)
    _prefetch_an_epoch(chaos_datasets, 2)
    assert set(seen) == _INPUT_SPANS
    assert all(s is _NOOP for got in seen.values() for s in got)
    assert list(tmp_path.iterdir()) == []


def test_staged_prefetch_records_the_fence_and_the_report_the_share(
    chaos_datasets, tmp_path,
):
    """With a staging ring: one prefetch.fence for every upload that was
    reported, on the staging thread, before the collate that rewrites the
    slot (or when the ring is settled); the report gives the staged share."""
    from cst_captioning_tpu.data.batcher import Batcher
    from cst_captioning_tpu.data.prefetch import StagingRing, prefetch_to_device

    class Uploaded:
        def block_until_ready(self):
            return self

    obs.configure(str(tmp_path / "run"), run="t")
    batcher = Batcher(chaos_datasets, batch_size=4, max_len=8, mode="video")
    n, ring = batcher.num_batches(), StagingRing(0)
    assert n > 2
    for _ in range(2):
        got = list(prefetch_to_device(
            batcher.epoch(staging=ring), size=1, place=False, staging=ring,
            transform=lambda b: Uploaded(),
        ))
        assert len(got) == n
    obs.shutdown()
    evs = json.load(open(tmp_path / "run" / "trace.json"))["traceEvents"]
    by = {name: [e for e in evs if e["name"] == name]
          for name in ("prefetch.stage", "prefetch.fence", "data.collate")}
    assert len(by["prefetch.fence"]) == 2 * n
    assert {e["tid"] for e in by["prefetch.fence"]} == {"prefetch"}
    in_stage = [f for f in by["prefetch.fence"]
                if any(_inside(f, st) for st in by["prefetch.stage"])]
    assert len(in_stage) == 2 * (n - 2)         # the rest: settling, 2 an epoch
    for f in in_stage:
        stage = next(st for st in by["prefetch.stage"] if _inside(f, st))
        collate = next(c for c in by["data.collate"] if _inside(c, stage))
        assert f["ts"] + f["dur"] <= collate["ts"] + 1.0
    rep = report_run(str(tmp_path / "run"))
    # an h5 dataset: no gather ran on the pool
    assert rep["collate"] == {"staged": 2 * n - 2, "fresh": 2,
                              "staged_share": (2 * n - 2) / (2 * n),
                              "blocks": 0, "pool_width": 0}
    text = render_report(rep)
    assert f"{2 * n - 2} batch(es) into reused staging slots, 2 into fresh" \
        in text
    assert "every gather on the collating thread" in text


def test_pooled_collate_is_one_span_a_batch_on_the_staging_thread(
    tmp_path, small_blocks,
):
    """A cached dataset whose feature gathers run over row blocks on the
    gather pool: still one data.collate a batch, on the staging thread, inside
    its prefetch.stage; the pool's threads record nothing at all; the report
    says how many blocks ran and on how wide a pool."""
    from cst_captioning_tpu.data import (
        Batcher,
        CaptionDataset,
        batcher as batcher_module,
        make_synthetic_dataset,
    )
    from cst_captioning_tpu.data.prefetch import prefetch_to_device

    synth = make_synthetic_dataset(
        str(tmp_path / "synth"), num_videos=12, modalities={"resnet": 16},
        max_frames=4, seed=5,
    )
    ds = CaptionDataset(synth["info_json"], {"resnet": synth["resnet"]},
                        "train", 4, cache_features=True)
    width = batcher_module._gather_pool()[1]
    blocks0 = obs.counter("data.collate.blocks").snapshot()

    obs.configure(str(tmp_path / "run"), run="t")
    batcher = Batcher(ds, batch_size=4, max_len=8, mode="video")
    n = len(list(prefetch_to_device(
        batcher.epoch(), size=2, transform=lambda b: (b.feats, b.feat_masks),
    )))
    assert n == batcher.num_batches() > 1
    obs.shutdown()
    ran = obs.counter("data.collate.blocks").snapshot() - blocks0
    assert ran == 2 * n             # a row is 256 bytes: two rows a block
    evs = json.load(open(tmp_path / "run" / "trace.json"))["traceEvents"]
    collates = [e for e in evs if e["name"] == "data.collate"]
    stages = [e for e in evs if e["name"] == "prefetch.stage"]
    assert len(collates) == len(stages) == n
    assert {e["tid"] for e in collates} == {"prefetch"}
    for stage in stages:
        assert sum(_inside(c, stage) for c in collates) == 1
    assert not [e for e in evs
                if str(e.get("tid", "")).startswith("collate.gather")]
    rep = report_run(str(tmp_path / "run"))
    assert rep["collate"]["blocks"] == obs.counter(
        "data.collate.blocks").snapshot()
    assert rep["collate"]["pool_width"] == width
    assert (f"{int(rep['collate']['blocks'])} gather block(s) on a pool of "
            f"{width} thread(s)") in render_report(rep)
    ds.close()


@pytest.mark.parametrize("enabled", [True, False], ids=["obs_on", "obs_off"])
def test_rl_epoch_spans_name_the_turnover_and_the_reward_parts(
    chaos_datasets, tmp_path, monkeypatch, enabled,
):
    """A tiny pipelined RL run through Trainer.train_rl. Obs on: keys, drain
    and read-back once an epoch, the reward span's three parts inside every
    rl.reward and summing to no more than it. Obs off: the same call sites
    get the shared no-op and nothing is written."""
    from cst_captioning_tpu.config.config import (
        DataConfig,
        EvalConfig,
        ExperimentConfig,
        ModelConfig,
        RLConfig,
        TrainConfig,
    )
    from cst_captioning_tpu.obs.span import _NOOP
    from cst_captioning_tpu.train.trainer import Trainer

    train_ds = chaos_datasets
    ckpt, run_dir = str(tmp_path / "ckpt"), str(tmp_path / "obs")
    epochs = 2
    cfg = ExperimentConfig(
        name="rl-spans",
        model=ModelConfig(
            vocab_size=len(train_ds.vocab), modalities=(("resnet", 16),),
            d_embed=16, d_hidden=16, d_att=8, encoder="meanpool",
            dropout=0.0, max_len=8, max_frames=4, dtype="float32",
        ),
        data=DataConfig(batch_size=4, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, ckpt_dir=ckpt, seed=0, epochs=0, eval_every_epochs=100,
            obs=enabled, obs_dir=run_dir,
        ),
        rl=RLConfig(enabled=True, num_rollouts=2, lr=1e-3, epochs=epochs,
                    pipelined=True),
        eval=EvalConfig(beam_size=1, max_len=8),
    )
    seen = _spy_on_spans(monkeypatch)
    tr = Trainer(cfg, train_ds, None, log_path=ckpt + "/ev.jsonl",
                 use_mesh=False)
    tr.train_rl()
    tr.close()
    obs.shutdown()
    assert _RL_SPANS | {"prefetch.wait", "rl.reward"} <= set(seen)
    if not enabled:
        assert all(s is _NOOP for got in seen.values() for s in got)
        assert not os.path.exists(run_dir)
        return
    sp = spans_of(read_events(run_dir))
    count = collections.Counter(s["name"] for s in sp)
    for name in ("rl.epoch.keys", "rl.epoch.drain", "ckpt.readback",
                 "rl.epoch"):
        assert count[name] == epochs, name
    assert {s["parent"] for s in sp if s["name"] == "ckpt.readback"} == {"ckpt"}
    assert {s["parent"] for s in sp
            if s["name"] == "rl.epoch.drain"} == {"rl.epoch"}
    rewards = [s for s in sp if s["name"] == "rl.reward"]
    steps = epochs * -(-len(train_ds.records) // cfg.data.batch_size)
    assert len(rewards) == steps
    parts = ("rl.reward.readback", "rl.reward.observe", "rl.reward.score")
    for name in parts:
        assert count[name] == steps
        assert {s["parent"] for s in sp if s["name"] == name} == {"rl.reward"}
    # events land in end order: a reward's parts are the three before it on
    # its own thread (a stage of the prefetch worker may end among them)
    for i, s in enumerate(sp):
        if s["name"] == "rl.reward":
            mine = [q for q in sp[:i] if q["thread"] == s["thread"]][-3:]
            assert tuple(p["name"] for p in mine) == parts
            assert sum(p["dur"] for p in mine) <= s["dur"] + 3e-6
            assert s["self_dur"] == pytest.approx(
                s["dur"] - sum(p["dur"] for p in mine), abs=5e-6)
    # every get of the epoch's batches, the end marker's included
    assert count["prefetch.wait"] == steps + epochs


def test_one_prefetch_worker_serves_a_phase_and_the_report_says_so(
    chaos_datasets, tmp_path,
):
    """XE then RL through the Trainer: each phase's first epoch starts the
    prefetch worker cold and every later one finds it on its batches; the
    worker draws an epoch's order once, stops at the phase's last epoch and
    is gone after it; the report's overlap section carries the line."""
    import threading

    from cst_captioning_tpu.config.config import (
        DataConfig,
        EvalConfig,
        ExperimentConfig,
        ModelConfig,
        RLConfig,
        TrainConfig,
    )
    from cst_captioning_tpu.train.trainer import Trainer

    train_ds = chaos_datasets
    ckpt, run_dir = str(tmp_path / "ckpt"), str(tmp_path / "obs")
    xe, rl = 2, 3
    cfg = ExperimentConfig(
        name="feed",
        model=ModelConfig(
            vocab_size=len(train_ds.vocab), modalities=(("resnet", 16),),
            d_embed=16, d_hidden=16, d_att=8, encoder="meanpool",
            dropout=0.0, max_len=8, max_frames=4, dtype="float32",
        ),
        data=DataConfig(batch_size=4, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, ckpt_dir=ckpt, seed=0, epochs=xe, eval_every_epochs=100,
            obs=True, obs_dir=run_dir,
        ),
        rl=RLConfig(enabled=True, num_rollouts=2, lr=1e-3, epochs=rl,
                    pipelined=True),
        eval=EvalConfig(beam_size=1, max_len=8),
    )
    tr = Trainer(cfg, train_ds, None, log_path=ckpt + "/ev.jsonl",
                 use_mesh=False)
    tr.train_xe()
    assert not [t for t in threading.enumerate() if t.name == "prefetch"]
    tr.train_rl()
    assert not [t for t in threading.enumerate() if t.name == "prefetch"]
    assert tr.batcher.epoch_index == xe - 1     # pinned by the main thread only
    tr.close()
    obs.shutdown()
    sp = spans_of(read_events(run_dir))
    # (the one more is Trainer.__init__'s sample batch, on the main thread)
    orders = collections.Counter(s["parent"] for s in sp
                                 if s["name"] == "data.epoch_order")
    assert orders == {"prefetch.stage": xe + rl, "setup": 1}
    rep = report_run(run_dir)
    assert rep["prefetch"] == {"carried": xe - 1 + rl - 1, "cold": 2,
                               "dropped": 0}
    assert ("prefetch: 3 epoch(s) found their first batches staged by the "
            "worker of the epoch before, 2 started it cold; 0 staged "
            "batch(es) dropped") in render_report(rep)
    # the pipelined RL loop runs on across every epoch's end but the phase's
    assert rep["rl_epochs"] == {"primed": rl - 1, "cold": 1}
    assert ("rl epochs: 2 began with the pipeline primed inside the drain of "
            "the epoch before, 1 with it empty") in render_report(rep)


def test_spans_land_in_a_profiler_trace(tmp_path):
    """While a recorder is installed a nesting span is also a profiler
    annotation: a jax.profiler trace taken meanwhile holds host events of
    the spans' names, on the threads they ran on; a track= window (it may
    end on another thread) is not annotated."""
    import glob
    import threading

    import jax

    obs.configure(str(tmp_path / "run"), run="t")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"), profiler_options=opts)
    try:
        def background():
            with obs.span("span_on_worker"):
                time.sleep(0.002)

        t = threading.Thread(target=background)
        t.start()
        with obs.span("span_outer"):
            with obs.span("span_inner", tag=1):
                time.sleep(0.002)
        obs.span("span_cancelled").begin().cancel()
        obs.span("span_window", track="virtual").begin().end()
        t.join()
    finally:
        jax.profiler.stop_trace()
    obs.shutdown()
    (path,) = glob.glob(str(tmp_path / "trace" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = [
        {e.name: (e.start_ns, e.duration_ns) for e in line.events
         if e.name.startswith("span_")}
        for plane in jax.profiler.ProfileData.from_file(path).planes
        for line in plane.lines
    ]
    lines = [names for names in lines if names]
    assert sorted(map(sorted, lines)) == [
        ["span_cancelled", "span_inner", "span_outer"], ["span_on_worker"]]
    (main,) = [names for names in lines if "span_outer" in names]
    (o0, od), (i0, idur) = main["span_outer"], main["span_inner"]
    assert o0 <= i0 and i0 + idur <= o0 + od and idur >= 2e6
    # the recorder's own stream is unchanged by the annotations
    names = [s["name"] for s in spans_of(read_events(str(tmp_path / "run")))]
    assert sorted(names) == ["span_inner", "span_on_worker", "span_outer",
                             "span_window"]
    assert names.index("span_inner") < names.index("span_outer")


def test_spans_record_without_the_profiler(tmp_path, monkeypatch):
    """jax.profiler missing: the recorder installs, spans record as before."""
    import sys

    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    rec = obs.configure(str(tmp_path / "run"), run="t")
    assert rec.annotation is None
    with obs.span("outer"):
        with obs.span("inner"):
            pass
    obs.shutdown()
    sp = spans_of(read_events(str(tmp_path / "run")))
    assert [(s["name"], s.get("parent")) for s in sp] == [
        ("inner", "outer"), ("outer", None)]
