"""Serving subsystem: continuous batching, paged bank, drain, parity.

The load-bearing pin is ACCEPTANCE PARITY: a request admitted mid-flight
into the continuous-batching engine must emit token- AND logprob-bit-
identical output to the same clip decoded offline through
``decoding.fused.fused_decode`` — the admission/compaction seam must not
perturb RNG streams or attention reads. Everything else (pages, traffic,
drain/restore, NPAD selection, obs) hangs off the same tiny model.
"""

import dataclasses
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.config.config import EOS_ID, PAD_ID, ModelConfig
from cst_captioning_tpu.decoding.fused import fused_decode
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.resilience.chaos import Fault, FaultPlan
from cst_captioning_tpu.serving import (
    CaptionService,
    ClipRequest,
    OutOfPages,
    PageBank,
    Trace,
    TrafficSpec,
    load_snapshot,
    make_trace,
)
from cst_captioning_tpu.serving.traffic import synth_request_features

MODAL = (("resnet", 16),)
T = 12
MAX_F = 8


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=97, modalities=MODAL, d_embed=16, d_hidden=16, d_att=8,
        encoder="temporal_attention", dropout=0.0, max_len=T,
        max_frames=MAX_F, dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats0 = {"resnet": jnp.asarray(rng.normal(size=(1, MAX_F, 16)),
                                    jnp.float32)}
    masks0 = {"resnet": jnp.ones((1, MAX_F), jnp.float32)}
    params = model.init(
        jax.random.key(0), feats0, masks0, jnp.zeros((1, T), jnp.int32)
    )
    # EOS-biased so caption lengths vary (the continuous-batching regime);
    # shared by every path, so parity comparisons are unaffected
    bias = params["params"]["cell"]["out_proj"]["bias"]
    params["params"]["cell"]["out_proj"]["bias"] = bias.at[EOS_ID].add(2.0)
    return model, params


def _requests(frames=(1, 8, 3, 8, 2, 5), seed0=1000):
    out = []
    for i, F in enumerate(frames):
        rng = np.random.default_rng(100 + i)
        out.append(ClipRequest(
            req_id=f"r{i}",
            feats={"resnet": rng.normal(size=(F, 16)).astype(np.float32)},
            masks={"resnet": np.ones((F,), np.float32)},
            seed=seed0 + i,
        ))
    return out


def _offline(model, params, req, K=2, min_len=0):
    """The parity oracle: the clip decoded alone through fused.py, padded
    to max_frames like every offline caller pads."""
    pad = model.cfg.max_frames - req.num_frames
    f1 = {"resnet": jnp.asarray(
        np.pad(np.asarray(req.feats["resnet"], np.float32),
               ((0, pad), (0, 0)))[None]
    )}
    m1 = {"resnet": jnp.asarray(
        np.pad(np.asarray(req.masks["resnet"], np.float32), ((0, pad),))[None]
    )}
    g, gl, s, sl = jax.tree.map(np.asarray, fused_decode(
        model, params, f1, m1, jax.random.key(req.seed), num_rollouts=K,
        min_len=min_len,
    ))
    return (np.concatenate([g, s[:, 0]], axis=0),
            np.concatenate([gl, sl[:, 0]], axis=0))


def _assert_parity(model, params, report, reqs, K=2, min_len=0, lp_ulp=0):
    """Tokens bit-equal to the offline decode, always. Log-probabilities
    bit-equal too, except where the engine's encode runs a one-row matrix
    product that the offline [1, max_frames] encode runs with eight rows
    (``lp_ulp=2``): XLA's CPU backend takes a vector-matrix kernel at M=1
    that rounds the last bit differently from its M>=2 product (4.8e-7 on
    values near 2.6), a property of the backend and not of the engine."""
    for req in reqs:
        tok, lp = _offline(model, params, req, K=K, min_len=min_len)
        res = report.results[req.req_id]
        np.testing.assert_array_equal(res.tokens, tok, err_msg=req.req_id)
        if lp_ulp:
            np.testing.assert_array_max_ulp(res.logprobs, lp, maxulp=lp_ulp)
        else:
            np.testing.assert_array_equal(res.logprobs, lp, err_msg=req.req_id)


# ---- traffic ----------------------------------------------------------------


def test_trace_is_deterministic_and_replayable(tmp_path):
    spec = TrafficSpec(kind="poisson", rate_rps=5.0, num_requests=16,
                       seed=3, frame_choices=(1, 4, 8))
    a, b = make_trace(spec), make_trace(spec)
    assert a.items == b.items and len(a) == 16
    assert all(
        x.arrival_s <= y.arrival_s for x, y in zip(a.items, a.items[1:])
    )
    path = str(tmp_path / "trace.json")
    a.save(path)
    assert Trace.load(path).items == a.items
    # feature payloads regenerate bit-identically from the item seed
    f1, m1 = synth_request_features(a.items[0], MODAL)
    f2, _ = synth_request_features(a.items[0], MODAL)
    np.testing.assert_array_equal(f1["resnet"], f2["resnet"])
    assert m1["resnet"].shape == (a.items[0].num_frames,)


def test_bursty_trace_modulates_rate():
    spec = TrafficSpec(kind="bursty", rate_rps=10.0, num_requests=64,
                       seed=1, burst_factor=8.0, burst_len_s=1.0)
    t = make_trace(spec)
    # burst windows (even seconds) hold far more arrivals than quiet ones
    burst = sum(1 for i in t.items if int(i.arrival_s) % 2 == 0)
    assert burst > len(t) * 0.7


def test_traffic_spec_validation():
    with pytest.raises(ValueError, match="kind"):
        TrafficSpec(kind="steady")
    with pytest.raises(ValueError, match="rate"):
        TrafficSpec(rate_rps=0.0)
    with pytest.raises(ValueError, match="burst"):
        TrafficSpec(kind="bursty", burst_factor=0.5)


# ---- page bank --------------------------------------------------------------


def test_page_bank_alloc_free_accounting():
    bank = PageBank(num_pages=6, page_size=4)
    p1 = bank.alloc("a", 9)     # ceil(9/4) = 3 pages
    assert len(p1) == 3 and bank.pages_in_use == 3
    assert 0 not in p1          # page 0 is the reserved zero page
    p2 = bank.alloc("b", 4)
    assert len(p2) == 1 and bank.pages_in_use == 4
    with pytest.raises(OutOfPages):
        bank.alloc("c", 12)     # 3 pages needed, 2 free
    with pytest.raises(ValueError, match="already holds"):
        bank.alloc("a", 4)
    table = bank.table(["a", "b", None], width=3)
    assert table.shape == (3, 3)
    np.testing.assert_array_equal(table[0], p1)
    assert table[1, 0] == p2[0] and (table[1, 1:] == 0).all()
    assert (table[2] == 0).all()
    bank.free("a")
    assert bank.pages_in_use == 1 and bank.free_pages == 5
    assert bank.alloc("c", 12) and bank.pages_in_use == 4
    snap = bank.snapshot()
    assert snap["page_size"] == 4 and set(snap["owned"]) == {"b", "c"}


# ---- the acceptance pin: mid-flight admission parity ------------------------


def test_midflight_admission_is_bit_identical_to_offline(setup):
    """capacity 2 << 6 ragged requests: most requests are admitted into
    lanes freed mid-flight while other requests sit at arbitrary local
    steps. Token AND logprob parity with the offline B=1 fused decode pins
    that the admission/compaction seam perturbs nothing."""
    model, params = setup
    reqs = _requests()
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    report = svc.serve(reqs)
    assert report.completed == len(reqs) and not report.drained
    # continuous batching actually happened: more strides than one batch
    # of 2 would need, and every slot was reused
    assert report.strides > (T // 4)
    _assert_parity(model, params, report, reqs)
    # all pages and slots returned
    assert svc.bank.pages_in_use == 0 and len(svc._free_slots) == 2


def test_serving_parity_with_min_len(setup):
    """min_len rides per-ROW in the serving step (each request's own local
    t), matching offline ``apply_min_len`` bit-for-bit."""
    model, params = setup
    reqs = _requests(frames=(2, 8, 5))
    report = CaptionService(
        model, params, capacity=2, num_rollouts=2, stride=4, min_len=3,
    ).serve(reqs)
    for res in report.results.values():
        lens = (res.tokens != PAD_ID).sum(axis=1)
        assert (lens >= 3).all()
    _assert_parity(model, params, report, reqs, min_len=3)


def test_serving_pallas_kernel_parity(setup):
    """The stride-kernel path (per-row mem_lens raggedness, in-kernel
    selection, kernel_block_b=1) is bit-identical to the same clips decoded
    offline through the pallas stride path."""
    model, params = setup
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=4,
    ))
    reqs = _requests()
    report = CaptionService(
        m_pal, params, capacity=2, num_rollouts=2, stride=4, frame_bucket=2,
    ).serve(reqs)
    _assert_parity(m_pal, params, report, reqs)


def test_page_table_stress_adversarial_ragged(setup):
    """1-frame and max-frame clips interleaved through a pool deliberately
    too small to hold the working set: admission backpressures on pages,
    every request still completes with bit-exact tokens, and the bank
    drains back to empty. ``frame_bucket=1`` encodes a 1-frame clip as one
    row where the oracle pads it to eight: log-probabilities to 2 ulp
    (``_assert_parity``)."""
    model, params = setup
    frames = [1, 8, 1, 8, 1, 8, 1, 8, 1, 8]
    reqs = _requests(frames=frames, seed0=7000)
    svc = CaptionService(
        model, params, capacity=4, num_rollouts=1, stride=4, frame_bucket=1,
        page_size=2, num_pages=6,  # 12 slots: < 2 max-frame clips' worth
    )
    report = svc.serve(reqs)
    assert report.completed == len(reqs)
    _assert_parity(model, params, report, reqs, K=1, lp_ulp=2)
    assert svc.bank.pages_in_use == 0
    assert svc.bank.pages_hwm <= 6


def test_single_request_larger_than_pool_raises(setup):
    model, params = setup
    svc = CaptionService(model, params, capacity=2, num_rollouts=1,
                         page_size=2, num_pages=2)
    with pytest.raises(OutOfPages, match="more pages than the whole pool"):
        svc.serve(_requests(frames=(8,)))


# ---- paged in-kernel attention: pool past the dense footprint ---------------


def test_paged_pool_exceeds_dense_footprint_with_staging(setup):
    """THE paged acceptance pin: a pool of 20 pages over 2 lanes x 4
    pages/row (dense footprint 8) actually FILLS — encode-ahead staging
    parks encoded requests' pages while lanes are busy, the high-water
    mark exceeds what any dense [B, W, E] bank could hold, and every
    request is still token- and logprob-bit-identical to its offline
    decode. The dense-gather path refuses the same pool at construction
    — the in-kernel page reader is what makes the surplus admissible."""
    model, params = setup
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=4,
    ))
    reqs = _requests(frames=(8,) * 8, seed0=9000)
    svc = CaptionService(
        m_pal, params, capacity=2, num_rollouts=1, stride=4, frame_bucket=1,
        page_size=2, num_pages=20,
    )
    assert svc.paged and svc.B * svc.table_width == 8
    report = svc.serve(reqs)
    assert report.completed == len(reqs) and not report.drained
    # the pool genuinely held more than one batch's dense-bank worth
    assert svc.bank.pages_hwm > svc.B * svc.table_width
    _assert_parity(m_pal, params, report, reqs, K=1)
    assert svc.bank.pages_in_use == 0 and len(svc._free_slots) == 2
    # the same pool is impossible on the gather path: it re-materializes
    # every lane's full window per stride, so surplus pages never admit
    with pytest.raises(ValueError, match="dense-bank footprint"):
        CaptionService(model, params, capacity=2, num_rollouts=1,
                       stride=4, frame_bucket=1, page_size=2, num_pages=20)


def test_paged_requires_kernel_path(setup):
    """paged=True without the stride kernel is a loud constructor error —
    the XLA decode has no in-kernel page reader to honor it."""
    model, params = setup
    with pytest.raises(ValueError, match="decode_impl='pallas'"):
        CaptionService(model, params, capacity=2, num_rollouts=1,
                       paged=True)


def test_paged_hot_swap_midflight_parity(setup):
    """Cross-version strides on the paged path: a publish straddling live
    traffic dispatches one masked paged stride per live version, and every
    request stays bit-identical to its offline decode under its
    admission-pinned params."""
    model, params = setup
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=4,
    ))
    p2 = _perturbed(params)
    reqs = _requests()
    svc = CaptionService(m_pal, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    assert svc.paged
    published = []

    def feedback(req, result, version):
        if not published:
            published.append(svc.publish_params(p2, version=1))

    svc._feedback = feedback
    report = svc.serve(reqs)
    assert published == [True]
    assert report.completed == len(reqs) and not report.drained
    by_ver = {0: [], 1: []}
    for req in reqs:
        by_ver[report.results[req.req_id].param_version].append(req)
    assert by_ver[0] and by_ver[1]
    _assert_parity(m_pal, params, report, by_ver[0])
    _assert_parity(m_pal, p2, report, by_ver[1])
    assert svc._old_params == {}


def test_npad_best_lane_selection(setup):
    """NPAD anytime-quality: the served caption is the best-scoring lane,
    so its total logprob is >= the greedy lane's by construction, and the
    caption ids are that lane's tokens up to EOS."""
    model, params = setup
    reqs = _requests(frames=(4, 8, 6), seed0=4000)
    report = CaptionService(
        model, params, capacity=3, num_rollouts=3, temperature=1.3,
    ).serve(reqs)
    for res in report.results.values():
        sums = res.logprobs.sum(axis=1)
        assert sums[res.best_lane] == sums.max()
        assert sums[res.best_lane] >= sums[0]
        row = res.tokens[res.best_lane]
        expect = []
        for tok in row:
            if tok in (EOS_ID, PAD_ID):
                break
            expect.append(int(tok))
        assert res.caption_ids == expect
        assert set(res.phases) == {"queue_wait", "encode", "decode", "detok"}


def test_batched_admission_encode_group_parity(setup):
    """admit_group > 1 batches same-bucket admission encodes into one pass;
    at f32 the encoder gemm is row-stable over M >= 2, so tokens hold
    bit-for-bit (the knob's contract — bf16-on-CPU is documented out). The
    oracle's B=1 encode runs the carry init ``[1, E] @ [E, H]`` as a
    vector-matrix product, the group's as ``[4, E] @ [E, H]``:
    log-probabilities to 2 ulp (``_assert_parity``)."""
    model, params = setup
    reqs = _requests(frames=(8, 8, 8, 8), seed0=5000)
    report = CaptionService(
        model, params, capacity=4, num_rollouts=2, admit_group=4,
    ).serve(reqs)
    _assert_parity(model, params, report, reqs, lp_ulp=2)


# ---- drain / snapshot / recovery --------------------------------------------


def test_serving_preempt_chaos_drains_and_recovers_bit_identical(
    setup, tmp_path
):
    """The seeded ``serving_preempt`` fault drains the loop mid-flight:
    in-flight strides finish, admissions stop, queue + page table persist.
    Replaying the drained queue through a fresh service completes the
    remaining requests BIT-identically to the undrained run."""
    model, params = setup
    reqs = _requests()
    base = CaptionService(model, params, capacity=2, num_rollouts=2,
                          stride=4, frame_bucket=2).serve(reqs)

    snap = str(tmp_path / "drain")
    plan = FaultPlan([Fault("serving.step", "serving_preempt", at=3)])
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    with plan.activate():
        drained = svc.serve(_requests(), snapshot_dir=snap)
    assert plan.fired and plan.fired[0]["kind"] == "serving_preempt"
    assert drained.drained and drained.drain_reason == "chaos_serving_preempt"
    assert drained.completed < len(reqs)
    assert os.path.exists(os.path.join(snap, "manifest.json"))
    assert os.path.exists(os.path.join(snap, "queue.npz"))

    restored = load_snapshot(snap)
    assert len(restored) == len(reqs) - drained.completed
    replay = CaptionService(model, params, capacity=2, num_rollouts=2,
                            stride=4, frame_bucket=2).serve(restored)
    union = dict(drained.results)
    union.update(replay.results)
    assert set(union) == set(base.results)
    for rid, res in base.results.items():
        np.testing.assert_array_equal(union[rid].tokens, res.tokens, rid)
        np.testing.assert_array_equal(union[rid].logprobs, res.logprobs, rid)


def test_snapshot_records_page_table_and_order(setup, tmp_path):
    model, params = setup
    import json

    snap = str(tmp_path / "drain2")
    plan = FaultPlan([Fault("serving.step", "serving_preempt", at=2)])
    svc = CaptionService(model, params, capacity=2, num_rollouts=1,
                         stride=4, frame_bucket=2)
    with plan.activate():
        svc.serve(_requests(), snapshot_dir=snap)
    manifest = json.load(open(os.path.join(snap, "manifest.json")))
    assert manifest["drain_reason"] == "chaos_serving_preempt"
    pt = manifest["page_table"]
    assert pt["num_pages"] == svc.bank.num_pages
    # stride-boundary drain: requests were genuinely IN FLIGHT at the cut
    assert manifest["in_flight_steps"]
    # in-flight requests lead the persisted order (admitted earlier)
    inflight = set(manifest["in_flight_steps"])
    lead = [r["req_id"] for r in manifest["requests"][:len(inflight)]]
    assert set(lead) == inflight


def test_snapshot_replays_onto_regrown_service(setup, tmp_path):
    """ISSUE 17 serving arc: a drained shard's queue+page snapshot replays
    onto a rejoined node — the replacement service comes up at the reduced
    width the outage left it, grows its lane pool back at a stride seam
    (pages added to the bank, lanes born finished), and completes the
    drained requests bit-identically to an undrained full-width run."""
    model, params = setup
    reqs = _requests()
    base = CaptionService(model, params, capacity=4, num_rollouts=2,
                          stride=4, frame_bucket=2).serve(reqs)

    snap = str(tmp_path / "regrow")
    plan = FaultPlan([Fault("serving.step", "serving_preempt", at=3)])
    svc = CaptionService(model, params, capacity=4, num_rollouts=2,
                         stride=4, frame_bucket=2)
    with plan.activate():
        drained = svc.serve(_requests(), snapshot_dir=snap)
    assert drained.drained and drained.completed < len(reqs)

    # the rejoined node starts at the degraded width, then grows back to
    # full width before admissions resume
    regrown = CaptionService(model, params, capacity=2, num_rollouts=2,
                             stride=4, frame_bucket=2)
    pages_before = regrown.bank.num_pages
    restored = load_snapshot(snap, service=regrown, grow_to=4)
    assert len(restored) == len(reqs) - drained.completed
    assert regrown.B == 4 and len(regrown._free_slots) == 4
    assert (regrown.bank.num_pages
            == pages_before + 2 * regrown.table_width)
    replay = regrown.serve(())  # the replayed queue is already submitted
    union = dict(drained.results)
    union.update(replay.results)
    assert set(union) == set(base.results)
    for rid, res in base.results.items():
        np.testing.assert_array_equal(union[rid].tokens, res.tokens, rid)
        np.testing.assert_array_equal(
            union[rid].logprobs, res.logprobs, rid
        )


def test_grow_capacity_with_live_state_preserves_parity(setup):
    """Growing the lane pool between serve calls (live lane state present)
    pads every lane-axis leaf with finished, empty lanes: later requests
    admitted at the grown width still decode bit-identically to the
    offline oracle, and shrinking in place is refused."""
    model, params = setup
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    first = _requests(frames=(1, 8, 3), seed0=1000)
    r1 = svc.serve(first)
    assert r1.completed == 3 and svc._state is not None
    svc.grow_capacity(5)
    assert svc.B == 5 and len(svc._free_slots) == 5
    second = [
        dataclasses.replace(r, req_id="g" + r.req_id)
        for r in _requests(frames=(8, 2, 5, 4, 6), seed0=2000)
    ]
    r2 = svc.serve(second)
    assert set(r2.results) >= {r.req_id for r in second}
    _assert_parity(model, params, r2, second)
    with pytest.raises(ValueError, match="only grows"):
        svc.grow_capacity(2)


def test_sigterm_drains_the_loop(setup, tmp_path):
    """A real SIGTERM mid-serve stops at the next stride boundary via the
    PreemptionHandler path (drain_reason='sigterm')."""
    model, params = setup
    import signal

    snap = str(tmp_path / "sig")
    svc = CaptionService(model, params, capacity=1, num_rollouts=1, stride=4)
    # many requests through one lane: plenty of stride boundaries
    reqs = _requests(frames=(8,) * 6, seed0=6000)
    timer = threading.Timer(
        0.05, lambda: os.kill(os.getpid(), signal.SIGTERM)
    )
    timer.start()
    try:
        report = svc.serve(reqs, snapshot_dir=snap)
    finally:
        timer.cancel()
    assert report.drained and report.drain_reason == "sigterm"
    assert report.completed < len(reqs)
    assert len(load_snapshot(snap)) == len(reqs) - report.completed


# ---- zero-sync discipline ---------------------------------------------------


def test_serving_loop_is_transfer_guard_clean(setup):
    """The warmed admission/decode loop holds under
    ``jax.transfer_guard("disallow")``: every host<->device crossing in the
    serving loop is explicit (device_put up, one device_get down per
    stride) — the empirical half of the GL001-clean claim."""
    model, params = setup
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    svc.serve(_requests(frames=(2, 8)))          # warm: compiles stage eagerly
    with jax.transfer_guard("disallow"):
        report = svc.serve(_requests(frames=(1, 8, 3), seed0=9000))
    assert report.completed == 3


# ---- obs --------------------------------------------------------------------


def test_serving_obs_events_and_report(setup, tmp_path):
    """A served run under obs leaves per-request phase histograms and
    span/request events that cli.obs_report aggregates into the serving
    section."""
    from cst_captioning_tpu import obs
    from cst_captioning_tpu.obs import metrics as obs_metrics
    from cst_captioning_tpu.obs.report import report_run, render_report

    model, params = setup
    obs_metrics.REGISTRY.reset()
    run_dir = str(tmp_path / "obsrun")
    obs.configure(run_dir, run="serve-test")
    try:
        CaptionService(model, params, capacity=2, num_rollouts=1,
                       stride=4).serve(_requests(frames=(2, 8, 5)))
        obs.snapshot_metrics()
    finally:
        obs.shutdown()
    rep = report_run(run_dir)
    sv = rep["serving"]
    assert sv is not None
    assert sv["submitted"] == 3 and sv["completed"] == 3
    assert sv["strides"] >= 1
    assert sv["phases"]["decode"]["count"] == 3
    assert sv["phases"]["queue_wait"]["count"] == 3
    text = render_report(rep)
    assert "serving: 3 submitted" in text
    # engine-loop spans land in the phase table
    names = {p["phase"] for p in rep["phases"]}
    assert {"serving.stride", "serving.encode"} <= names


def test_serving_drain_dumps_postmortem_with_slo_snapshot(setup, tmp_path):
    """PR 13 satellite: a drained service leaves a flight-recorder
    postmortem bundle whose registry carries the SLO snapshot, next to the
    obs event stream, renderable by cli.obs_report --postmortem."""
    from cst_captioning_tpu import obs
    from cst_captioning_tpu.obs import metrics as obs_metrics
    from cst_captioning_tpu.obs.report import load_postmortem

    model, params = setup
    obs_metrics.REGISTRY.reset()
    run_dir = str(tmp_path / "obsrun")
    obs.configure(run_dir, run="serve-drain")
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    svc.set_slo(30.0)
    plan = FaultPlan([Fault("serving.step", "serving_preempt", at=3)])
    try:
        with plan.activate():
            report = svc.serve(_requests(),
                               snapshot_dir=str(tmp_path / "drain"))
    finally:
        obs.shutdown()
    assert report.drained

    (bundle,) = [
        n for n in os.listdir(run_dir) if n.startswith("postmortem_")
    ]
    assert bundle.endswith("serving_drain_chaos_serving_preempt")
    pm = load_postmortem(os.path.join(run_dir, bundle))
    assert pm["verified"], pm["problems"]
    meta = pm["meta"]
    assert meta["drain_reason"] == "chaos_serving_preempt"
    assert meta["pending"] + meta["inflight"] > 0  # drained mid-flight
    sv = pm["registry"]["serving"]
    assert sv["drain_reason"] == "chaos_serving_preempt"
    assert sv["slo"] is not None and sv["slo"]["target_s"] == 30.0
    # the bundle names the param version that served (fleet attribution)
    assert sv["param_version"] == 0 and sv["param_swaps"] == 0
    snap = obs_metrics.snapshot()
    assert snap["counters"].get("serving.drain_postmortem_error") is None


# ---- SLO burn-rate monitor (Obs v2) -----------------------------------------


def test_slo_monitor_burn_rates_and_edge_triggered_alerts():
    """Multi-window burn-rate math on a fake clock: attainment/burn gauges,
    the breach counter, and the edge-triggered alert (fires once per
    excursion when BOTH windows burn hot; re-fires for a new excursion)."""
    from cst_captioning_tpu.obs import metrics as obs_metrics
    from cst_captioning_tpu.serving.engine import SloMonitor

    obs_metrics.REGISTRY.reset()
    mon = SloMonitor(0.1, objective=0.9, windows=(10.0, 100.0),
                     fast_burn=2.0, slow_burn=1.5)
    # 9 ok + 1 breach: attainment 0.9 == objective -> burning exactly at
    # budget (1.0x), no alert
    for i in range(9):
        mon.observe(0.05, now=float(i))
    mon.observe(0.5, now=9.0)
    snap = obs_metrics.snapshot()
    assert snap["gauges"]["serving.slo.attainment.10s"] == pytest.approx(0.9)
    assert snap["gauges"]["serving.slo.burn_rate.10s"] == pytest.approx(1.0)
    assert snap["counters"]["serving.slo.breaches"] == 1
    assert mon.alerts == 0

    # sustained breaches push BOTH windows over threshold: ONE alert for
    # the excursion, counted through the shared anomaly spelling
    for i in range(10, 16):
        mon.observe(0.5, now=float(i))
    snap = obs_metrics.snapshot()
    assert mon.alerts == 1
    assert snap["counters"]["serving.slo.alerts"] == 1
    assert snap["counters"]["obs.anomaly.slo_burn"] == 1

    # recovery clears the latch; a fresh excursion re-alerts
    for i in range(16, 40):
        mon.observe(0.01, now=float(i))
    assert mon.alerts == 1
    for i in range(40, 52):
        mon.observe(0.5, now=float(i))
    assert mon.alerts == 2

    # window expiry: 200s of silence ages everything out of both windows
    assert mon.burn_rate(10.0, now=260.0) == 0.0
    assert mon.burn_rate(100.0, now=260.0) == 0.0


def test_slo_monitor_validates_parameters():
    from cst_captioning_tpu.serving.engine import SloMonitor

    with pytest.raises(ValueError):
        SloMonitor(0.0)
    with pytest.raises(ValueError):
        SloMonitor(0.1, objective=1.0)
    with pytest.raises(ValueError):
        SloMonitor(0.1, windows=(600.0, 60.0))  # fast must be < slow


def test_service_set_slo_gauges_and_snapshot(setup):
    """set_slo arms the monitor after calibration (bench_serving's flow):
    served completions populate the target/attainment/burn gauges and
    slo_snapshot(); target <= 0 disarms."""
    from cst_captioning_tpu.obs import metrics as obs_metrics

    model, params = setup
    obs_metrics.REGISTRY.reset()
    svc = CaptionService(model, params, capacity=2, num_rollouts=1, stride=4)
    assert svc.slo_snapshot() is None  # disarmed by default
    svc.set_slo(30.0)  # generous target: every request lands within
    svc.serve(_requests(frames=(2, 8, 5)))
    snap = obs_metrics.snapshot()
    assert snap["gauges"]["serving.slo.target_s"] == 30.0
    assert snap["gauges"]["serving.slo.attainment.60s"] == 1.0
    assert snap["gauges"]["serving.slo.burn_rate.60s"] == 0.0
    assert snap["counters"].get("serving.slo.breaches") is None
    s = svc.slo_snapshot()
    assert s["target_s"] == 30.0 and s["breach_alerts"] == 0
    assert s["burn_rate"] == {"60s": 0.0, "600s": 0.0}
    svc.set_slo(0.0)
    assert svc.slo_snapshot() is None


# ---- drain-free hot param swap (online RL feedback loop) --------------------


def _perturbed(params, tok=5, delta=3.0):
    """A second param version whose captions visibly differ: copy the tree
    containers (leaves shared) and raise one output-bias logit."""
    p2 = jax.tree.map(lambda x: x, params)
    bias = p2["params"]["cell"]["out_proj"]["bias"]
    p2["params"]["cell"]["out_proj"]["bias"] = bias.at[tok].add(delta)
    return p2


def test_hot_param_swap_midflight_parity(setup):
    """THE swap acceptance pin: a publish landing while requests are in
    flight applies at a stride boundary; every request — admitted before OR
    after the swap — is token- and logprob-bit-identical to the offline
    fused decode under its admission-pinned params. The straddle window
    exercises mixed-version strides (one masked dispatch per live
    version)."""
    model, params = setup
    p2 = _perturbed(params)
    reqs = _requests()
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    svc.set_slo(30.0)
    published = []

    def feedback(req, result, version):
        if not published:
            published.append(svc.publish_params(p2, version=1))

    svc._feedback = feedback
    report = svc.serve(reqs)
    assert published == [True]
    assert report.completed == len(reqs) and not report.drained
    assert svc.param_version == 1
    assert len(svc._swap_history) == 1
    by_ver = {0: [], 1: []}
    for req in reqs:
        by_ver[report.results[req.req_id].param_version].append(req)
    # the swap genuinely straddled live traffic
    assert by_ver[0] and by_ver[1]
    _assert_parity(model, params, report, by_ver[0])
    _assert_parity(model, p2, report, by_ver[1])
    # the two versions really produce different captions (non-vacuous)
    assert any(
        not np.array_equal(_offline(model, params, r)[0],
                           _offline(model, p2, r)[0])
        for r in by_ver[1]
    )
    # the outgoing tree was retired once its last pinned lane completed
    assert svc._old_params == {}
    # slo snapshot names the active version
    assert svc.slo_snapshot()["param_version"] == 1
    # a replayed/stale publish is refused, not applied
    assert not svc.publish_params(params, version=1)
    assert svc.param_version == 1 and svc._pending_publish is None


def test_param_swap_chaos_preempt_refuses_never_tears(setup, tmp_path):
    """The seeded ``param_swap`` fault preempts EXACTLY mid-swap (after the
    publish staged, before application): the swap must be fully refused —
    active version unchanged, pending publish cleared — and the drained
    queue replays bit-identically under the OLD params."""
    model, params = setup
    p2 = _perturbed(params)
    reqs = _requests()
    base = CaptionService(model, params, capacity=2, num_rollouts=2,
                          stride=4, frame_bucket=2).serve(reqs)

    snap = str(tmp_path / "swapdrain")
    plan = FaultPlan([Fault("serving.param_swap", "param_swap", at=0)])
    svc = CaptionService(model, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    published = []

    def feedback(req, result, version):
        if not published:
            published.append(svc.publish_params(p2, version=1))

    svc._feedback = feedback
    with plan.activate():
        drained = svc.serve(_requests(), snapshot_dir=snap)
    assert plan.fired and plan.fired[0]["kind"] == "param_swap"
    assert drained.drained and drained.drain_reason == "chaos_param_swap"
    # fully refused: no version change, no torn half-applied state
    assert svc.param_version == 0 and svc._pending_publish is None
    assert svc._swap_history == [] and svc._old_params == {}
    # everything served (before and during the drain) ran under v0
    assert all(
        r.param_version == 0 for r in drained.results.values()
    )
    restored = load_snapshot(snap)
    replay = CaptionService(model, params, capacity=2, num_rollouts=2,
                            stride=4, frame_bucket=2).serve(restored)
    union = dict(drained.results)
    union.update(replay.results)
    assert set(union) == set(base.results)
    for rid, res in base.results.items():
        np.testing.assert_array_equal(union[rid].tokens, res.tokens, rid)
        np.testing.assert_array_equal(union[rid].logprobs, res.logprobs, rid)


def test_param_swap_obs_report_renders_versions(setup, tmp_path):
    """An applied swap lands in the run report's serving section (version
    gauge + swap counter) and the text rendering."""
    from cst_captioning_tpu import obs
    from cst_captioning_tpu.obs import metrics as obs_metrics
    from cst_captioning_tpu.obs.report import report_run, render_report

    model, params = setup
    p2 = _perturbed(params)
    obs_metrics.REGISTRY.reset()
    run_dir = str(tmp_path / "obsswap")
    obs.configure(run_dir, run="serve-swap")
    try:
        svc = CaptionService(model, params, capacity=2, num_rollouts=1,
                             stride=4)
        published = []

        def feedback(req, result, version):
            if not published:
                published.append(svc.publish_params(p2))

        svc._feedback = feedback
        svc.serve(_requests(frames=(2, 8, 5)))
        obs.snapshot_metrics()
    finally:
        obs.shutdown()
    rep = report_run(run_dir)
    sv = rep["serving"]
    assert sv["param_swaps"] == 1 and sv["param_swaps_refused"] == 0
    assert sv["param_version"] == 1.0
    assert "param swaps: 1 applied (active v1)" in render_report(rep)


# ---- bf16 batched-admission fallback ----------------------------------------


def test_bf16_admission_group_falls_back_to_per_request(setup):
    """admit_group > 1 promises row-stable grouped encodes; bf16 gemms are
    not row-stable, so a bf16 service demotes to per-request admission
    encode (the parity-preserving path) and counts the fallback. f32 keeps
    the grouped path (bit-exactness pinned above)."""
    model, params = setup
    m_bf16 = CaptionModel(dataclasses.replace(model.cfg, dtype="bfloat16"))
    svc = CaptionService(m_bf16, params, capacity=4, num_rollouts=1,
                         admit_group=4)
    assert svc.requested_admit_group == 4 and svc.admit_group == 1
    report = svc.serve(_requests(frames=(8, 8, 8, 8), seed0=5000))
    assert report.completed == 4
    svc32 = CaptionService(model, params, capacity=4, num_rollouts=1,
                           admit_group=4)
    assert svc32.requested_admit_group == 4 and svc32.admit_group == 4


# ---- pallas stride-kernel path: grow / snapshot-regrow ----------------------


def test_pallas_grow_capacity_with_live_state_preserves_parity(setup):
    """grow_capacity with live lane state on the pallas stride-kernel path
    (kernel_block_b=1 per-row raggedness): requests admitted at the grown
    width still decode bit-identically to the offline pallas oracle."""
    model, params = setup
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=4,
    ))
    svc = CaptionService(m_pal, params, capacity=2, num_rollouts=2,
                         stride=4, frame_bucket=2)
    r1 = svc.serve(_requests(frames=(1, 8, 3), seed0=1000))
    assert r1.completed == 3 and svc._state is not None
    svc.grow_capacity(4)
    assert svc.B == 4 and len(svc._free_slots) == 4
    second = [
        dataclasses.replace(r, req_id="g" + r.req_id)
        for r in _requests(frames=(8, 2, 5, 4), seed0=2000)
    ]
    r2 = svc.serve(second)
    assert set(r2.results) >= {r.req_id for r in second}
    _assert_parity(m_pal, params, r2, second)


@pytest.mark.slow  # heaviest pallas compile chain; the grow-parity test
#                    above keeps the pallas grow seam in tier-1
def test_pallas_snapshot_replays_onto_regrown_service(setup, tmp_path):
    """load_snapshot(grow_to=) on the pallas stride-kernel path: a drained
    shard's queue replays onto a degraded-width pallas service grown back
    to full width, bit-identical to the undrained full-width run."""
    model, params = setup
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=4,
    ))
    reqs = _requests()
    base = CaptionService(m_pal, params, capacity=4, num_rollouts=2,
                          stride=4, frame_bucket=2).serve(reqs)

    snap = str(tmp_path / "palregrow")
    plan = FaultPlan([Fault("serving.step", "serving_preempt", at=3)])
    svc = CaptionService(m_pal, params, capacity=4, num_rollouts=2,
                         stride=4, frame_bucket=2)
    with plan.activate():
        drained = svc.serve(_requests(), snapshot_dir=snap)
    assert drained.drained and drained.completed < len(reqs)

    regrown = CaptionService(m_pal, params, capacity=2, num_rollouts=2,
                             stride=4, frame_bucket=2)
    restored = load_snapshot(snap, service=regrown, grow_to=4)
    assert len(restored) == len(reqs) - drained.completed
    assert regrown.B == 4 and len(regrown._free_slots) == 4
    replay = regrown.serve(())
    union = dict(drained.results)
    union.update(replay.results)
    assert set(union) == set(base.results)
    for rid, res in base.results.items():
        np.testing.assert_array_equal(union[rid].tokens, res.tokens, rid)
        np.testing.assert_array_equal(union[rid].logprobs, res.logprobs, rid)
