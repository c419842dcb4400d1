"""The fourth decoder kind (``ModelConfig.decoder = "eva"``, models/eva.py;
ops/eva_attention.py) against its plain reference (benchmark/reference_eva.py),
at a small size on seeded random weights, float32 stated, with windows of 8
and chunks of 2 so that windows roll over the prefix and every caption of 12
steps crosses one: teacher forcing and prefill then single steps through the
per-lane state that changes kind, clips whose prefix ends on a chunk's edge,
on a window's edge and off both, and a model whose one window holds prefix
and caption whole; the pooling from its definition; the flash kernel
(interpret mode) against the walk over query blocks; beam search with a
clip's shared state held once against it copied a lane and against the
reference's own; the other three decoder kinds' beam outputs against what the
parent commit emitted. Then the seams: the ``Evaluator``'s gauges, counters
and its prefill as a program of its own, ``cli/eval.py`` on the
configuration's eval preset, ``obs/flops.py``, ``cli.obs_report``'s table.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.config import get_preset
from cst_captioning_tpu.config.config import ModelConfig
from cst_captioning_tpu.decoding import beam_search
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import EncoderOutput
from cst_captioning_tpu.obs import flops
from cst_captioning_tpu.ops import eva_attention as eva

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T, F = 12, 48
TINY = dict(
    decoder="eva", vocab_size=32, modalities=(("patch", 16),), max_len=T,
    max_frames=F, dtype="float32", param_dtype="float32", hidden_size=32,
    num_hidden_layers=3, intermediate_size=48, num_attention_heads=4,
    rms_norm_eps=1e-5, rope_theta=100000.0, window_size=8, chunk_size=2,
    num_pred_heads=8, init_std=0.3)
# the same model with one window that holds prefix and caption whole: no
# position ever leaves it and EVA is plain causal attention
WINDOWS = {"rolls": {}, "one_window": {"window_size": 64}}
# valid slots a clip: off every edge (a chunk straddles n), on a chunk's edge,
# on a window's edge, the whole prefix (a window's edge too), and two more
N = np.array([35, 36, 40, 48, 27, 42])
B = len(N)


@pytest.fixture(scope="module")
def ref():
    """The reference, loaded from its file as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        "reference_eva", os.path.join(ROOT, "benchmark", "reference_eva.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _as_file(cfg: ModelConfig) -> dict:
    return json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))


def _batch(n=N, seed=1, holes=False):
    rng = np.random.default_rng(seed)
    mask = (np.arange(F)[None] < n[:, None]).astype(np.float32)
    if holes:
        mask = np.stack([rng.permutation(row) for row in mask])
    feats = {"patch": rng.normal(size=(len(n), F, 16)).astype(np.float32)}
    labels = rng.integers(4, TINY["vocab_size"], size=(len(n), T)).astype(np.int32)
    return feats, {"patch": mask}, labels


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The prefix's FFN row blocks at a scale the tiny model crosses."""
    from cst_captioning_tpu.models import eva as eva_model

    patch = pytest.MonkeyPatch()
    patch.setattr(eva_model, "FFN_ROWS", 64)
    yield
    patch.undo()


@pytest.fixture(scope="module", params=sorted(WINDOWS))
def setup(request):
    cfg = ModelConfig(**{**TINY, **WINDOWS[request.param]})
    model = CaptionModel(cfg)
    feats, masks, labels = _batch()
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def _picked(logits, labels):
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    return np.take_along_axis(np.asarray(logp), labels[..., None], -1)[..., 0]


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_init_declares_every_parameter_without_a_forward(setup):
    _cfg, _model, params, *_ = setup
    dec = params["params"]["decoder"]
    assert set(dec) == {"embed_patch", "embed_tokens", "norm", "lm_head",
                        "layers_0", "layers_1", "layers_2"}
    assert dec["lm_head"].shape == (32, 8 * 32)         # eight blocks of ids
    assert dec["layers_0"]["phi"].shape == dec["layers_0"]["mu"].shape == (4, 8)
    assert dec["layers_0"]["k_proj"].shape == (32, 32)
    # the norms' unit offset: g starts at 0
    assert not np.asarray(dec["norm"]).any()
    assert not np.asarray(dec["layers_1"]["input_layernorm"]).any()


def test_teacher_forced_logits_match_the_reference(setup, ref):
    """``__call__`` against the reference's full forward, logits compared:
    float32 on both sides, so what is left is summation order (a logit reads
    up to 6; 1.3e-5 is the largest difference seen)."""
    cfg, model, params, feats, masks, labels = setup
    logits = jax.jit(model.apply)(params, feats, masks, labels)
    assert logits.shape == (B, T, cfg.vocab_size) and logits.dtype == jnp.float32
    inputs = np.concatenate([np.ones((B, 1), np.int32), labels[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p: ref.forward(
            p, _as_file(cfg), feats, masks, jnp.asarray(inputs),
            lambda x: x))(params))
    assert np.abs(want).max() > 3.0         # a peaked distribution, not noise
    np.testing.assert_allclose(np.asarray(logits), want, atol=1e-4)
    picked = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(logits, labels), picked, atol=5e-5)


def test_prefill_then_one_token_at_a_time_through_the_state_matches_the_full_forward(
        setup, ref):
    """T single steps on the carry (caption keys that grow, summaries a lane
    makes as its chunks complete and reads once it has left their window)
    against the reference's full forward, which has no cache. With windows of
    8 every one of the six clips crosses a window inside its 12 steps."""
    cfg, model, params, feats, masks, labels = setup
    spec = eva.EvaSpec(cfg.window_size, cfg.chunk_size)
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    W, S = min(cfg.window_size, F), eva.own_slots(T, spec)
    assert S == 7
    # once a clip, head-major: every chunk's summary, the last window's keys
    assert [k.shape for k in enc.memory[0]] == [(B, 4, F // 2, 8)] * 3
    near_k, near_v, start = enc.memory_proj
    assert [k.shape for k in near_k] == [(B, 4, W, 8)] * 3 == [v.shape for v in near_v]
    want_start = np.clip(N // cfg.window_size * cfg.window_size, 0, F - W)
    assert np.asarray(start).tolist() == want_start.tolist()
    # a lane's own
    # a lane's own: its keys in a frame of S whole chunks that begins at the
    # chunk n falls in (the prefix's last key first where n is odd)
    assert [k.shape for k in enc.carry.k] == [(B, 4, 2 * S, 8)] * 3
    assert [k.shape for k in enc.carry.ks] == [(B, 4, S, 8)] * 3
    first = np.asarray(enc.carry.k[0])[:, :, 0]
    assert first[0].any() and not first[1:4].any()      # n = 35; 36, 40, 48
    assert all(x.shape[0] == B for x in jax.tree.leaves(enc.carry))
    assert np.asarray(enc.memory_mask.sum(1)).astype(int).tolist() == N.tolist()
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    carry, prev, got, counted = enc.carry, np.full((B,), 1, np.int32), [], []
    step = jax.jit(lambda p, c, tok: model.apply(
        p, c, tok, bank, method=CaptionModel.decode_step))
    for t in range(T):
        carry, logits = step(params, carry, jnp.asarray(prev))
        got.append(np.asarray(logits))
        counted.append(np.asarray(carry.counted)[:, 0])
        prev = labels[:, t]
    assert np.asarray(carry.pos).tolist() == [T] * B
    inputs = np.concatenate([np.ones((B, 1), np.int32), labels[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p: ref.forward(
            p, _as_file(cfg), feats, masks, jnp.asarray(inputs),
            lambda x: x))(params))
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-4)
    # what each step counted is the two sets' sizes at its position
    counted = np.stack(counted, 1)                          # [B, T, 3]
    pos = N[:, None] + np.arange(T)[None]
    window, per = cfg.window_size, cfg.window_size // cfg.chunk_size
    assert (counted[..., 0] == pos % window + 1).all()
    assert (counted[..., 1] == per * (pos // window)).all()
    crossed = (pos % window == 0) & (np.arange(T)[None] > 0)
    assert (counted[..., 2] == crossed).all()
    assert crossed.any(1).all() == (cfg.window_size == 8)
    if cfg.window_size == 8:
        # the state changed kind: the summaries a lane made of its own chunks
        # are the pooling of the keys it holds, chunk by chunk
        i, n = 1, int(N[1])                     # n = 36: the caption's chunks
        layer = params["params"]["decoder"]["layers_0"]
        k = np.asarray(carry.k[0])[i, :, :T].transpose(1, 0, 2)     # [T, H, d]
        v = np.asarray(carry.v[0])[i, :, :T].transpose(1, 0, 2)
        ks, vs = eva.pool_chunks(k.reshape(T // 2, 2, 4, 8),
                                 v.reshape(T // 2, 2, 4, 8),
                                 layer["phi"], layer["mu"])
        np.testing.assert_allclose(
            np.asarray(carry.ks[0])[i, :, :T // 2].transpose(1, 0, 2),
            np.asarray(ks), atol=1e-6)
        np.testing.assert_allclose(
            np.asarray(carry.vs[0])[i, :, :T // 2].transpose(1, 0, 2),
            np.asarray(vs), atol=1e-6)


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_missing_slots_are_as_if_they_were_not_there(setup, ref):
    """A clip whose missing slots lie anywhere reads as the clip of its valid
    slots in order: in the program and in the reference alike."""
    cfg, model, params, _f, _m, labels = setup
    feats, masks, _ = _batch(seed=5, holes=True)
    order = np.argsort(masks["patch"] == 0, axis=1, kind="stable")
    packed = {"patch": np.take_along_axis(feats["patch"], order[..., None], 1)}
    packed_mask = {"patch": np.take_along_axis(masks["patch"], order, 1)}
    apply = jax.jit(model.apply)
    got = np.asarray(apply(params, feats, masks, labels))
    np.testing.assert_array_equal(
        got, np.asarray(apply(params, packed, packed_mask, labels)))
    want = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(got, labels), want, atol=5e-5)


def _attention_case(P=56, rows=2, H=3, d=8, seed=0):
    keys = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(key, (rows, P, H, d)) for key in keys[:3])
    phi, mu = (0.3 * jax.random.normal(key, (H, d)) for key in keys[3:])
    return q, k, v, phi, mu, jnp.array([P, 37])


def test_a_chunk_s_summary_is_its_definition():
    q, k, v, phi, mu, _n = _attention_case()
    spec = eva.EvaSpec(window=16, chunk=4)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, spec)
    assert ks.shape == vs.shape == (2, 14, 3, 8)
    kc, vc = np.asarray(k)[:, 20:24], np.asarray(v)[:, 20:24]     # chunk 5
    a = np.einsum("bjhd,hd->bjh", kc, np.asarray(phi))
    a = np.exp(a - a.max(1, keepdims=True))
    a = a / a.sum(1, keepdims=True)
    np.testing.assert_allclose(np.asarray(ks[:, 5]), np.einsum(
        "bjh,bjhd->bhd", a, kc) + np.asarray(mu), atol=1e-5)
    np.testing.assert_allclose(np.asarray(vs[:, 5]),
                               np.einsum("bjh,bjhd->bhd", a, vc), atol=1e-5)
    assert eva.own_slots(128, eva.EvaSpec()) == 9
    assert eva.key_counts(jnp.int32(14336 + 5), eva.EvaSpec()) == (6, 896)


def test_the_walk_over_query_blocks_is_one_softmax_over_both_sets():
    """``impl="xla"`` against the sets written out as one mask over a dense
    product (the only ``[positions, positions]`` array in this file)."""
    q, k, v, phi, mu, n = _attention_case()
    spec, P, d = eva.EvaSpec(window=16, chunk=4), 56, 8
    ks, vs = eva.chunk_summaries(k, v, phi, mu, spec)
    i, j = jnp.arange(P)[:, None], jnp.arange(P)[None]
    exact = (j // 16 == i // 16) & (j <= i)
    pooled = jnp.arange(P // 4)[None] < 4 * (i // 16)
    s = jnp.concatenate([
        jnp.where(exact, jnp.einsum("bqhd,bkhd->bhqk", q, k), -1e30),
        jnp.where(pooled, jnp.einsum("bqhd,bchd->bhqc", q, ks), -1e30)],
        -1) / np.sqrt(d)
    p = jax.nn.softmax(s, -1)
    want = jnp.einsum("bhqk,bkhd->bqhd", p[..., :P], v) \
        + jnp.einsum("bhqc,bchd->bqhd", p[..., P:], vs)
    got = eva.eva_prefill(q, k, v, ks, vs, n, spec, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-6)


@pytest.mark.parametrize("tiles", [(8, 8), (16, 8), (8, 16), (4, 16), (16, 16)])
def test_flash_kernel_equals_the_walk_over_query_blocks(tiles):
    """The kernel (interpret mode) on 56 positions of windows of 16 (the last
    window is not whole: the kernel pads it) and rows whose last positions do
    not exist; the rows under ``n`` are compared, the others are nobody's."""
    q, k, v, phi, mu, n = _attention_case()
    spec = eva.EvaSpec(window=16, chunk=4)
    ks, vs = eva.chunk_summaries(k, v, phi, mu, spec)
    want = eva.eva_prefill(q, k, v, ks, vs, n, spec, impl="xla")
    got = eva.eva_prefill(q, k, v, ks, vs, n, spec, impl="pallas", tiles=tiles)
    live = (jnp.arange(56)[None] < n[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(got * live), np.asarray(want * live),
                               atol=2e-6)
    with pytest.raises(ValueError, match="divide the window"):
        eva.eva_prefill(q, k, v, ks, vs, n, spec, impl="pallas", tiles=(6, 8))


def test_beam_with_the_clip_s_state_held_once_emits_what_a_copy_a_lane_does(
        setup, ref):
    """"lanes" closes over the encoder output (one copy of a clip's summaries
    and of its last window's keys), "reference" tiles it a lane: the same
    tokens and tally; the scores agree to float32's last bits and not bit for
    bit, because a lane's one query a head is a matrix-vector product when
    the beams are rows of the batch and one row of a five-row product when
    they are lanes, and the CPU's two kernels sum the 8 terms in another
    order (no such product has a form that is the same in both layouts and
    still runs on the chip's matrix unit). Both emit the reference's own
    search's captions, with a window crossed inside every one of them."""
    cfg, model, params, feats, masks, _labels = setup
    out = {impl: jax.jit(lambda p, impl=impl: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl,
        return_tally=True))(params) for impl in ("lanes", "reference")}
    np.testing.assert_array_equal(np.asarray(out["lanes"][0]),
                                  np.asarray(out["reference"][0]))
    np.testing.assert_allclose(np.asarray(out["lanes"][1]),
                               np.asarray(out["reference"][1]), rtol=2e-6)
    np.testing.assert_array_equal(np.asarray(out["lanes"][2]),
                                  np.asarray(out["reference"][2]))
    exact, pooled, crossed = np.asarray(out["lanes"][2])[0]
    if cfg.window_size == 8:
        assert crossed >= 5 * B and pooled > exact   # every lane of every clip
    else:
        assert crossed == 0 and pooled == 0
    tokens, score = jax.jit(lambda p: ref.beam_search(
        p, _as_file(cfg), feats, masks, 5, T))(params)
    np.testing.assert_array_equal(np.asarray(out["lanes"][0]), np.asarray(tokens))
    np.testing.assert_allclose(np.asarray(out["lanes"][1]), np.asarray(score),
                               atol=1e-4)


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_beam_search_from_an_encoder_pass_of_its_own(setup):
    _cfg, model, params, feats, masks, _labels = setup
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    whole = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=3))(params)
    split = jax.jit(lambda p, e: beam_search(
        model, p, None, None, beam_size=3, enc=e))(params, enc)
    for a, b in zip(whole, split):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _golden():
    with open(os.path.join(ROOT, "tests", "golden_beam_pr41.json")) as f:
        return json.load(f)


def _other_kinds():
    from test_sparse_linear import _OTHER_KINDS
    from test_sparse_linear import TINY as SALA

    return {**_OTHER_KINDS, "sparse_linear": {
        **SALA, "modalities": (("resnet", 32), ("c3d", 16)), "max_len": 12,
        "max_frames": 8, "sparse_dense_len": 8}}


@pytest.mark.parametrize("impl", ["lanes", "reference"])
@pytest.mark.parametrize("kind", ["lstm", "latent_moe", "sparse_linear"])
def test_the_other_decoders_beam_outputs_are_the_parent_commit_s(kind, impl):
    """Tokens and score bits of beam 5 on seeded weights, as the commit
    before this decoder kind emitted them (tests/golden_beam_pr41.json,
    written by running these lines on that commit)."""
    model = CaptionModel(ModelConfig(**_other_kinds()[kind]))
    rng = np.random.default_rng(1)
    n = rng.integers(4, 9, size=6)
    mask = (np.arange(8)[None] < n[:, None]).astype(np.float32)
    feats = {name: (rng.normal(size=(6, 8, dim)) * mask[..., None]
                    ).astype(np.float32) for name, dim in (("resnet", 32), ("c3d", 16))}
    masks = {name: mask.copy() for name in ("resnet", "c3d")}
    labels = rng.integers(4, 64, size=(6, 12)).astype(np.int32)
    from test_sparse_linear import assert_the_golden_scores

    params = model.init(jax.random.key(0), feats, masks, labels)
    tokens, score = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl)[:2])(params)
    want = _golden()[f"{kind}.{impl}"]
    assert np.asarray(tokens).tolist() == want["tokens"]
    assert_the_golden_scores(kind, score, want["score_bits"])


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_the_lstm_only_entry_points_say_so(setup):
    from cst_captioning_tpu.serving.engine import CaptionService

    cfg, model, params, feats, masks, labels = setup
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    with pytest.raises(NotImplementedError, match="eva"):
        model.apply(params, enc, labels, method=CaptionModel.teacher_force_logps)
    with pytest.raises(ValueError, match="rl.enabled"):
        get_preset("evabyte_8l_xe").override(rl__enabled=True)
    with pytest.raises(ValueError, match="chunk_size"):
        bad = ModelConfig(**{**TINY, "chunk_size": 3})
        CaptionModel(bad).init(jax.random.key(0), feats, masks, labels)
    with pytest.raises(ValueError, match="unknown decoder"):
        ModelConfig(**{**TINY, "decoder": "evabyte"})
    with pytest.raises(NotImplementedError, match="eva"):
        CaptionService(model, params, None)


def test_the_preset_holds_the_published_widths():
    mc = get_preset("evabyte_8l_eval_beam5").model
    assert (mc.hidden_size, mc.intermediate_size, mc.vocab_size) == (4096, 11008, 320)
    assert (mc.num_attention_heads, mc.window_size, mc.chunk_size) == (32, 2048, 16)
    assert (mc.num_pred_heads, mc.rope_theta, mc.init_std) == (8, 100000.0, 0.01275)
    ev = get_preset("evabyte_8l_eval_beam5").eval
    assert (ev.max_len, ev.beam_size, ev.beam_impl, ev.prefill_program) == \
        (128, 5, "lanes", True)
    model = CaptionModel(mc)
    feats = {"patch": jnp.zeros((1, 8, 1024))}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), feats, {"patch": jnp.ones((1, 8))},
        jnp.zeros((1, 128), jnp.int32)))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    # 8 x 202,391,552 a layer; embedding 1.3 M, head 10.5 M, projector 4.2 M,
    # the last norm
    assert count == 8 * 202_391_552 + 320 * 4096 + 4096 * 2560 + 1024 * 4096 + 4096
    assert count == 1_635_127_296
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))


def test_flops_dispatch_on_the_decoder_kind():
    mc = get_preset("evabyte_8l_xe").model
    short = flops.eva_per_tok_flops(mc, context=100)
    late = flops.eva_per_tok_flops(mc, context=14336 + 64)
    # 2 x 8 layers of 202 M and the pairs: 100 exact keys; 64 + 896 summaries
    assert short == 8 * (2 * (4 * 4096**2 + 3 * 4096 * 11008) + 4 * 4096 * 100)
    assert late - short == 8 * 4 * 4096 * (64 + 896 - 100)
    assert flops.model_xe_flops_per_row(mc) > 3 * 16384 * short


# ---- the seams: Evaluator, checkpoints, cli/eval.py ---------------------------


def _tiny_overrides():
    return {"model__" + k: v for k, v in TINY.items() if k != "decoder"}


def _dataset(tmp_path, videos: int):
    from cst_captioning_tpu.data.dataset import CaptionDataset
    from cst_captioning_tpu.data.synthetic import make_synthetic_dataset

    paths = make_synthetic_dataset(
        str(tmp_path / "data"), num_videos=videos, vocab_words=24,
        modalities=dict(TINY["modalities"]), max_frames=F, splits=(1.0, 0.0),
        seed=3)
    return CaptionDataset(paths["info_json"], {"patch": paths["patch"]},
                          "train", F), paths


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_evaluator_tells_the_kinds_of_state_apart_and_counts_the_keys(
        tmp_path, setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.obs.report import build_report, render_report

    cfg, model, params, *_ = setup
    ds, _paths = _dataset(tmp_path, 12)
    base = dataclasses.replace(
        get_preset("evabyte_8l_eval_beam5").eval, max_len=T,
        metrics=("CIDEr-D",), split="train")
    obs.configure(str(tmp_path / "obs"), run="t")
    try:
        split = Evaluator(model, ds, base, batch_size=4).evaluate(params)
        snap = obs.snapshot()
        whole = Evaluator(model, ds, dataclasses.replace(
            base, prefill_program=False), batch_size=4).evaluate(params)
        tiled = Evaluator(model, ds, dataclasses.replace(
            base, prefill_program=False, beam_impl="reference"), batch_size=4)
        tiled.evaluate(params)
        snap_tiled = obs.snapshot()
    finally:
        obs.shutdown()
        ds.close()
    assert split["captions"] == whole["captions"] and len(split["captions"]) == 12
    g, c = snap["gauges"], snap["counters"]
    # 4 clips, 3 layers, 4 heads of 8, keys and values, float32: the last
    # window's exact keys once a clip and a caption's frame of 7 chunks a
    # lane; every prefix chunk's summary once a clip and 7 slots a lane
    cell = 3 * 2 * 4 * 8 * 4
    assert g["decode.window_bytes"] == cell * (4 * 8 + 4 * 5 * 14)
    assert g["decode.summary_bytes"] == cell * (4 * F // 2 + 4 * 5 * 7)
    assert g["decode.cache_bytes"] == \
        g["decode.window_bytes"] + g["decode.summary_bytes"]
    # copied a lane, the clip's part is five times as large
    assert snap_tiled["gauges"]["decode.window_bytes"] == \
        cell * (4 * 5 * 8 + 4 * 5 * 14)
    assert c["eva.keys_exact"] > 0 and c["eva.keys_summary"] > c["eva.keys_exact"]
    assert c["eva.window_crossings"] >= 12 * 5      # every lane of every clip
    events = [json.loads(line) for line in open(tmp_path / "obs" / "events.jsonl")]
    names = [e["name"] for e in events if e.get("event") == "span"]
    assert names.count("eval.prefill") == names.count("eval.decode") == 3
    text = render_report(build_report(events))
    assert "chunk summaries" in text and "entered a new window" in text


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_prefill_program_is_a_beam_search_on_one_device(setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator

    _cfg, model, *_ = setup
    ecfg = dataclasses.replace(get_preset("evabyte_8l_eval_beam5").eval,
                               beam_size=1)
    with pytest.raises(ValueError, match="prefill_program"):
        Evaluator(model, None, ecfg, batch_size=4)


def test_cli_eval_runs_the_eval_preset_end_to_end(tmp_path, capsys):
    """``cli/eval.py`` on the configuration's eval preset (tiny overrides):
    a checkpoint of seeded weights saved by the ``Trainer`` of its XE preset
    (``train_xe(epochs=0)`` is a no-op), loaded and decoded at beam 5. No
    entry point of its own, no option that picks an implementation."""
    from cst_captioning_tpu.cli import eval as cli_eval
    from cst_captioning_tpu.train.trainer import Trainer

    over = _tiny_overrides()
    ds, paths = _dataset(tmp_path, 6)
    cfg = get_preset("evabyte_8l_xe").override(
        **over, data__batch_size=2, train__ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(cfg, ds, None, use_mesh=False)
    assert trainer.train_xe(epochs=0) is None
    trainer.ckpt.save(jax.device_get(trainer.state), None)
    trainer.close()
    ds.close()
    args = ["--preset", "evabyte_8l_eval_beam5",
            "--info-json", paths["info_json"],
            "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-name", "latest",
            "--split", "train", "--results-json", str(tmp_path / "r.json"),
            "--feature", f"patch={paths['patch']}",
            "--set", "data__batch_size=2", "--set", f"eval__max_len={T}",
            "--set", "mesh__num_devices=1"]
    for key, value in over.items():
        args += ["--set", f"{key}={value!r}"]
    cli_eval.main(args)
    table = json.loads(capsys.readouterr().out)
    assert "CIDEr-D" in table and np.isfinite(table["CIDEr-D"])
    with open(tmp_path / "r.json") as f:
        assert len(json.load(f)["captions"]) == 6
