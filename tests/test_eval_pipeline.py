"""Eval fast-path tests: the two-stage decode/score pipeline is bit-identical
to the serial evaluator (metric table AND captions), the overlap ledger is
recorded, the NPAD eval mode runs end to end, and the decode loop is timed
from inside (a batch's spans share its identity, they cover the pass, and
``eval.starved_seconds`` is the gaps they show)."""

import json
import os
import threading
import time

import jax
import numpy as np
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.config.config import EvalConfig, ModelConfig
from cst_captioning_tpu.data.batcher import Batcher
from cst_captioning_tpu.data.dataset import CaptionDataset
from cst_captioning_tpu.data.synthetic import make_synthetic_dataset
from cst_captioning_tpu.eval.evaluator import Evaluator
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.train.steps import batch_arrays


@pytest.fixture(scope="module")
def eval_files(tmp_path_factory):
    out = tmp_path_factory.mktemp("evalpipe")
    return make_synthetic_dataset(
        str(out), num_videos=12, modalities={"resnet": 16}, max_frames=4,
        seed=2,
    )


@pytest.fixture(scope="module")
def eval_setup(eval_files):
    paths = eval_files
    ds = CaptionDataset(
        paths["info_json"], {"resnet": paths["resnet"]}, "test", 4
    )
    cfg = ModelConfig(
        vocab_size=len(ds.vocab), modalities=(("resnet", 16),), d_embed=12,
        d_hidden=12, d_att=6, encoder="temporal_attention", max_len=8,
        max_frames=4, dtype="float32",
    )
    model = CaptionModel(cfg)
    train_ds = CaptionDataset(
        paths["info_json"], {"resnet": paths["resnet"]}, "train", 4
    )
    batch = next(iter(
        Batcher(train_ds, batch_size=4, max_len=8).epoch(shuffle=False)
    ))
    feats, masks, labels, *_ = batch_arrays(batch)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return model, params, ds


def test_pipelined_matches_serial_bit_identical(eval_setup):
    """The tentpole contract: the pipelined evaluator's captions (content
    AND dict order) and metric table are bit-identical to the serial
    path's — overlap changes WHEN tokenization runs, never its result.
    Compared through json.dumps so any float drift in any metric fails."""
    model, params, ds = eval_setup
    serial = Evaluator(
        model, ds, EvalConfig(beam_size=3, max_len=8, pipelined=False),
        batch_size=5,
    ).evaluate(params)
    piped = Evaluator(
        model, ds,
        EvalConfig(beam_size=3, max_len=8, pipelined=True, score_workers=3),
        batch_size=5,
    ).evaluate(params)
    assert list(piped["captions"]) == list(serial["captions"])
    assert piped["captions"] == serial["captions"]
    assert json.dumps(piped["metrics"], sort_keys=True) == json.dumps(
        serial["metrics"], sort_keys=True
    )


def test_pipelined_beam_reference_impl_matches_lanes(eval_setup):
    """cfg.beam_impl="reference" routes the sequential oracle through the
    same evaluator — identical captions (the lane/reference bit-parity
    contract, observed at the eval surface)."""
    model, params, ds = eval_setup
    lanes = Evaluator(
        model, ds, EvalConfig(beam_size=3, max_len=8), batch_size=5
    ).evaluate(params)
    ref = Evaluator(
        model, ds,
        EvalConfig(beam_size=3, max_len=8, beam_impl="reference"),
        batch_size=5,
    ).evaluate(params)
    assert ref["captions"] == lanes["captions"]


def test_pipelined_records_overlap_ledger(eval_setup, tmp_path):
    """A pipelined eval leaves the obs ledger behind: stage histograms,
    overlap gauges, fill/drain spans — and cli.obs_report's builder
    surfaces them as the eval section."""
    from cst_captioning_tpu.obs.report import build_report, load_events

    model, params, ds = eval_setup
    run_dir = str(tmp_path / "run")
    obs.REGISTRY.reset()  # counters are cumulative; isolate this run
    obs.configure(run_dir, run="evalpipe")
    try:
        Evaluator(
            model, ds, EvalConfig(beam_size=2, max_len=8), batch_size=5
        ).evaluate(params)
    finally:
        obs.shutdown()
        obs.REGISTRY.reset()
    rep = build_report(load_events(run_dir))
    ev = rep["eval"]
    assert ev is not None
    assert ev["batches"] >= 1
    assert ev["captions"] == len(ds.records)
    assert ev["decode_total_s"] > 0.0 and ev["score_total_s"] > 0.0
    assert 0.0 <= ev["overlap_fraction"] <= 1.0
    assert 0.0 <= ev["overlap_efficiency"] <= 1.0
    names = {p["phase"] for p in rep["phases"]} | {
        p["phase"] for p in rep["overlap"]
    }
    assert {"eval.pipeline.refs", "eval.pipeline.fill",
            "eval.pipeline.drain"} <= names


# ---- the decode loop timed from inside (PR 40) ------------------------------

_BATCH_SPANS = ("data.collate", "eval.h2d", "eval.launch", "eval.collect")
_CALLER = {"phase": "rl", "epoch": 3, "step": 7}
_ROWS = 4       # a batch: 9 clips are two whole batches and a padded one


@pytest.fixture(scope="module")
def nine_clips(eval_files):
    return CaptionDataset(
        eval_files["info_json"], {"resnet": eval_files["resnet"]}, "train", 4)


def _evaluator(eval_setup, ds, pipelined):
    return Evaluator(
        eval_setup[0], ds,
        EvalConfig(beam_size=2, max_len=8, pipelined=pipelined,
                   score_workers=2),
        batch_size=_ROWS,
    )


@pytest.fixture(scope="module", params=[True, False],
                ids=["pipelined", "serial"])
def two_passes(request, eval_setup, nine_clips, tmp_path_factory):
    """Two ``evaluate()`` calls of one Evaluator under obs, on a thread that
    carries a caller's context (a Trainer's validator), with a span of the
    caller's after them -> the stream's events, the wall-clock window of
    each call, ``eval.starved_seconds`` after each, the results, the
    registry as ``metrics.prom`` has it, the spans' timeline."""
    from cst_captioning_tpu.obs.report import load_events

    _model, params, _ds = eval_setup
    run_dir = str(tmp_path_factory.mktemp("evalspans"))
    ev = _evaluator(eval_setup, nine_clips, request.param)
    obs.REGISTRY.reset()
    obs.configure(run_dir, run="evalspans")
    obs.set_context(**_CALLER)
    walls, starved, results = [], [], []
    try:
        for _ in range(2):
            t0 = obs.wall_time()
            results.append(ev.evaluate(params))
            walls.append((t0, obs.wall_time()))
            starved.append(obs.snapshot()["counters"]["eval.starved_seconds"])
            time.sleep(0.02)    # the caller's bookkeeping between two passes
        with obs.span("caller.after"):
            pass
        prom = obs.REGISTRY.to_prometheus()
    finally:
        obs.set_context(**dict.fromkeys(_CALLER))
        obs.shutdown()
        obs.REGISTRY.reset()
    spans = [dict(e, t1=e["ts"], t0=e["ts"] - e["dur"])
             for e in load_events(run_dir) if e.get("event") == "span"]
    # the same spans on the clock the counter reads (perf_counter, seconds):
    # an event's wall-clock stamp is taken when its line is written, later
    with open(os.path.join(run_dir, "trace.json")) as f:
        timeline = [(e["name"], e["ts"] / 1e6, (e["ts"] + e["dur"]) / 1e6)
                    for e in json.load(f)["traceEvents"]]
    return spans, walls, starved, results, prom, timeline


def test_a_batch_s_spans_share_its_identity(two_passes, nine_clips):
    """Every batch of every pass has exactly one collate, upload, launch and
    collect, all four under its ``(eval_pass, eval_batch)``; the caller's
    own context rides on them untouched and comes out as it went in."""
    spans, *_ = two_passes
    n_batches = -(-len(nine_clips.records) // _ROWS)
    assert n_batches == 3 and len(nine_clips.records) % _ROWS
    got = sorted((s["eval_pass"], s["eval_batch"], s["name"])
                 for s in spans if s["name"] in _BATCH_SPANS)
    assert got == sorted((p, b, name) for p in range(2)
                         for b in range(n_batches) for name in _BATCH_SPANS)
    ours = [s for s in spans if s["name"] in _BATCH_SPANS]
    assert all(s[k] == v for s in ours for k, v in _CALLER.items())
    assert {s["thread"] for s in ours} == {threading.current_thread().name}
    assert all(s["bytes"] > 0 for s in ours if s["name"] == "eval.h2d")
    (after,) = [s for s in spans if s["name"] == "caller.after"]
    assert "eval_pass" not in after and "eval_batch" not in after
    assert all(after[k] == v for k, v in _CALLER.items())
    # the pass's end (drain, scoring, snapshot) belongs to no batch
    assert all("eval_batch" not in s for s in spans
               if s["name"] in ("eval", "eval.score", "eval.pipeline.drain"))


def test_the_driving_thread_s_spans_cover_a_pass(two_passes):
    """What the loop's thread spent inside ``evaluate()`` has a name: its
    spans, the umbrella ``eval`` left out, cover 95 % of the calls' wall."""
    spans, walls, *_ = two_passes
    me = threading.current_thread().name
    named = wall = 0.0
    for w0, w1 in walls:
        cut = sorted((max(s["t0"], w0), min(s["t1"], w1)) for s in spans
                     if s["thread"] == me and s["name"] != "eval"
                     and s["t1"] > w0 and s["t0"] < w1)
        edge = w0
        for a, b in cut:        # the union of the clipped intervals
            named += max(b - max(a, edge), 0.0)
            edge = max(edge, b)
        wall += w1 - w0
    assert named >= 0.95 * wall, (named, wall)


def test_starved_seconds_is_the_gap_the_spans_show(two_passes):
    """``eval.starved_seconds`` = the stretches between a collect that
    leaves nothing launched and the next launch, read off the spans: none
    inside a pass (the next batch is launched before a batch is collected),
    one a turnover, the caller's time between two ``evaluate()`` in it. A
    pass's end settles the running stretch before its snapshot, so that a
    snapshot holds what was starved before it (the serial path takes none)."""
    _spans, _walls, starved, _results, prom, timeline = two_passes
    marks = sorted(
        [(t0, +1) for name, t0, _ in timeline if name == "eval.launch"]
        + [(t1, -1) for name, _, t1 in timeline if name == "eval.collect"])
    gaps, emptied, in_flight = [], [], 0
    for t, d in marks:
        if d > 0 and in_flight == 0 and emptied:
            gaps.append(t - emptied[-1])
        in_flight += d
        if in_flight == 0:
            emptied.append(t)       # a pass's last collect
    assert len(gaps) == 1 and gaps[0] > 0.02 and len(emptied) == 2
    # from a pass's last collect to its snapshot: counted by that snapshot
    snaps = sorted(t0 for name, t0, _ in timeline if name == "obs.snapshot")
    tails = [b - a for a, b in zip(emptied, snaps)] or [0.0, 0.0]
    # the first is a part of the one turnover; the second follows the last pass
    assert len(tails) == 2 and 0.0 <= tails[0] < gaps[0] and tails[1] >= 0.0
    assert starved[0] == pytest.approx(tails[0], abs=1e-3)
    assert starved[1] == pytest.approx(gaps[0] + tails[1], abs=1e-3)
    assert "eval_starved_seconds " in prom and "eval_h2d_bytes " in prom


def test_obs_off_nothing_is_stamped_or_counted(two_passes, eval_setup,
                                               nine_clips):
    """Without a recorder the loop keeps no stamp and no count, the counter
    does not come to be, and captions and table are the observed run's."""
    _spans, _walls, _starved, results, *_ = two_passes
    _model, params, _ds = eval_setup
    pipelined = "eval.pipeline.drain" in {s["name"] for s in _spans}
    ev = _evaluator(eval_setup, nine_clips, pipelined)
    obs.REGISTRY.reset()
    assert not obs.enabled()
    try:
        got = [ev.evaluate(params) for _ in range(2)]
        counters = obs.snapshot()["counters"]
    finally:
        obs.REGISTRY.reset()
    assert "eval.starved_seconds" not in counters
    assert "eval.h2d.bytes" not in counters
    assert (ev._passes, ev._in_flight, ev._drained) == (0, 0, None)
    for a, b in zip(got, results):
        assert list(a["captions"]) == list(b["captions"])
        assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)


def test_report_reads_fill_and_drain_off_the_spans(two_passes):
    """The report's eval section: fill and drain are the newest pass's two
    spans (no gauge keeps a second copy; the serial evaluator has no drain),
    the starved seconds stand against the stretch from the first pass's
    start to the last's end, and the upload is the counter over the batches."""
    from cst_captioning_tpu.obs.report import build_report, render_report

    spans, _walls, starved, *_ = two_passes
    events = [dict(s, event="span") for s in spans] + [{
        "event": "metrics", "ts": spans[-1]["ts"],
        "counters": {"eval.starved_seconds": starved[1], "eval.batches": 6.0,
                     "eval.h2d.bytes": 6e6},
        "gauges": {"eval.wall_s": 1.0},
        "histograms": {"eval.decode_seconds": {
            "buckets": [1.0], "counts": [6, 0], "sum": 0.6, "count": 6,
            "max": 0.2}}}]
    ev = build_report(events)["eval"]
    last = lambda name: ([s["dur"] for s in spans  # noqa: E731
                          if s["name"] == name] or [0.0])[-1]
    assert ev["fill_s"] == last("eval.pipeline.fill")
    assert ev["drain_s"] == last("eval.pipeline.drain")
    passes = [s for s in spans if s["name"] == "eval"]
    across = passes[-1]["t1"] - passes[0]["t0"]
    assert across > sum(s["dur"] for s in passes)   # the caller's turn is in it
    assert ev["starved_share"] == pytest.approx(starved[1] / across)
    text = render_report(build_report(events))
    assert "starved: " in text and "1.0 MB a batch" in text


def test_report_s_starved_share_under_a_trainer_s_validator():
    """Two validations with a long training between them: the starved
    seconds hold the training (the stamp lives on the ``Evaluator``), so the
    share stands against the stretch they lie in and says whose time it
    holds; against the passes' own wall it would read thousands of percent."""
    from cst_captioning_tpu.obs.report import build_report, render_report

    def eval_span(t0, t1):
        return {"event": "span", "name": "eval", "ts": t1, "dur": t1 - t0,
                "thread": "MainThread"}

    events = [eval_span(10.0, 12.0), eval_span(1010.0, 1012.0), {
        "event": "metrics", "ts": 1012.0,
        "counters": {"eval.starved_seconds": 998.9, "eval.batches": 4.0},
        "gauges": {"eval.wall_s": 2.0},
        "histograms": {"eval.decode_seconds": {
            "buckets": [1.0], "counts": [4, 0], "sum": 3.0, "count": 4,
            "max": 0.9}}}]
    report = build_report(events)
    assert report["eval"]["passes_span_s"] == pytest.approx(1002.0)
    assert report["eval"]["starved_share"] == pytest.approx(998.9 / 1002.0)
    assert "caller's time between passes included (99.7% of the 1002.000s" \
        in render_report(report)


def test_npad_eval_mode_end_to_end(eval_setup):
    """cfg.npad_lanes switches the evaluator to NPAD anytime decoding:
    every split video still gets a caption and the metric table is
    finite — and the run is deterministic (the per-batch rng is
    fold_in(key(npad_seed), batch_index), carrying no mutable state, so
    a repeat evaluate — pipeline thread timing and all — reproduces the
    captions exactly)."""
    model, params, ds = eval_setup
    cfg = EvalConfig(
        beam_size=1, max_len=8, npad_lanes=3, npad_temperature=1.0,
        npad_seed=7,
    )
    ev = Evaluator(model, ds, cfg, batch_size=5)
    r1 = ev.evaluate(params)
    r2 = ev.evaluate(params)
    assert set(r1["captions"]) == {r.video_id for r in ds.records}
    assert r1["captions"] == r2["captions"]
    assert all(np.isfinite(v) for v in r1["metrics"].values())


def test_eval_config_validation():
    with pytest.raises(ValueError, match="beam_impl"):
        EvalConfig(beam_impl="bogus")
    with pytest.raises(ValueError, match="npad_lanes"):
        EvalConfig(npad_lanes=-1)
    with pytest.raises(ValueError, match="npad_temperature"):
        EvalConfig(npad_lanes=2, npad_temperature=0.0)
    with pytest.raises(ValueError, match="score_workers"):
        EvalConfig(score_workers=0)
