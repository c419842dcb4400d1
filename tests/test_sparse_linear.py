"""The third decoder kind (``ModelConfig.decoder = "sparse_linear"``,
models/sparse_linear.py; ops/sparse_attention.py, ops/linear_attention.py)
against its plain reference (benchmark/reference_sparse_linear.py), at a small
size on seeded random weights, float32 stated: teacher forcing and prefill
then single steps through both kinds of state, with the selection's sizes set
so that it really prunes and so that it never does; the chunked linear scan
against the one-step recurrence; the flash kernel against the walk over query
blocks; beam search with a clip's prefix held once against it copied a lane,
and against the reference's own; the other two decoder kinds' beam outputs
against what the parent commit emitted. Then the seams: the ``Evaluator``'s
gauges, counters and its prefill as a program of its own, ``cli/eval.py`` on
the configuration's eval preset, ``obs/flops.py``.
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
from cst_captioning_tpu.ops import linear_attention as linear
from cst_captioning_tpu.ops import sparse_attention as sparse

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B, T, F = 4, 8, 48
TINY = dict(
    decoder="sparse_linear", vocab_size=64, modalities=(("patch", 16),),
    max_len=T, max_frames=F, dtype="float32", param_dtype="float32",
    hidden_size=32, num_hidden_layers=4, intermediate_size=48,
    num_attention_heads=4, num_key_value_heads=2, head_dim=8, lightning_nh=4,
    lightning_head_dim=8,
    mixer_types=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"),
    rms_norm_eps=1e-6, rope_theta=10000.0, initializer_range=0.3,
    scale_emb=12.0, scale_depth=1.4, dim_model_base=8, published_layers=32,
    first_layer_index=8, sparse_kernel_size=4, sparse_kernel_stride=2,
    sparse_block_size=4, sparse_topk=2, sparse_window_size=6,
    sparse_init_blocks=1, sparse_dense_len=16)
# the same model with a dense length no query reaches: nothing is pruned
SELECTION = {"prunes": {}, "never_prunes": {"sparse_dense_len": 4096}}


@pytest.fixture(scope="module")
def ref():
    """The reference, loaded from its file as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        "reference_sparse_linear",
        os.path.join(ROOT, "benchmark", "reference_sparse_linear.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _as_file(cfg: ModelConfig) -> dict:
    return json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))


def _batch(rows=B, seed=1, holes=False):
    """Clips with 24-48 valid slots of 48; ``holes`` scatters the missing
    slots through the clip instead of leaving them at its end."""
    rng = np.random.default_rng(seed)
    n = rng.integers(F // 2, F + 1, size=rows)
    mask = (np.arange(F)[None] < n[:, None]).astype(np.float32)
    if holes:
        mask = np.stack([rng.permutation(row) for row in mask])
    feats = {"patch": rng.normal(size=(rows, F, 16)).astype(np.float32)}
    labels = rng.integers(4, TINY["vocab_size"], size=(rows, T)).astype(np.int32)
    return feats, {"patch": mask}, labels


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The prefix's chunk and row-block sizes at a scale the tiny model
    crosses: 48 positions are three chunks, 4 x 48 rows three FFN blocks."""
    from cst_captioning_tpu.models import sparse_linear

    patch = pytest.MonkeyPatch()
    patch.setattr(sparse_linear, "LINEAR_CHUNK", 16)
    patch.setattr(sparse_linear, "FFN_ROWS", 64)
    yield
    patch.undo()


@pytest.fixture(scope="module", params=sorted(SELECTION))
def setup(request):
    cfg = ModelConfig(**{**TINY, **SELECTION[request.param]})
    model = CaptionModel(cfg)
    feats, masks, labels = _batch()
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def _picked(logits, labels):
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    return np.take_along_axis(np.asarray(logp), labels[..., None], -1)[..., 0]


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
def test_init_declares_every_parameter_without_a_forward(setup):
    _cfg, _model, params, *_ = setup
    dec = params["params"]["decoder"]
    assert set(dec) == {"embed_patch", "embed_tokens", "norm", "lm_head",
                        "layers_0", "layers_1", "layers_2", "layers_3"}
    assert dec["layers_0"]["k_proj"].shape == (32, 2 * 8)     # 2 kv heads
    assert dec["layers_1"]["k_proj"].shape == (32, 4 * 8)     # 4 linear heads
    assert "o_norm" in dec["layers_1"] and "o_norm" not in dec["layers_0"]
    assert dec["layers_0"]["o_gate"].shape == (32, 32)


def test_teacher_forced_logprobs_match_the_reference(setup, ref):
    cfg, model, params, feats, masks, labels = setup
    logits = jax.jit(model.apply)(params, feats, masks, labels)
    assert logits.shape == (B, T, cfg.vocab_size) and logits.dtype == jnp.float32
    want = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    assert np.abs(want).mean() > 1.0        # a peaked distribution, not noise
    np.testing.assert_allclose(_picked(logits, labels), want, atol=5e-5)


def test_prefill_then_one_token_at_a_time_through_both_states_match_the_full_forward(
        setup, ref):
    """(a), (b): T single steps on the carry (a growing key/value cache in
    the sparse layers, a fixed-size state in the linear ones) against the
    reference's full forward, which has no cache."""
    cfg, model, params, feats, masks, labels = setup
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    keys, values = enc.memory
    assert [k.shape for k in keys] == [(B, F, 2, 8)] * 2      # once a clip
    assert [c.shape for c in enc.memory_proj] == [(B, (F - 4) // 2 + 1, 2, 8)] * 2
    assert [k.shape for k in enc.carry.k] == [(B, T, 2, 8)] * 2    # a lane's own
    assert [s.shape for s in enc.carry.state] == [(B, 4, 8, 8)] * 2
    assert all(s.dtype == jnp.float32 for s in enc.carry.state)
    assert all(x.shape[0] == B for x in jax.tree.leaves(enc.carry))
    n = masks["patch"].sum(1).astype(int)
    assert np.asarray(enc.memory_mask.sum(1)).astype(int).tolist() == n.tolist()
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    carry, prev, got = enc.carry, np.full((B,), 1, np.int32), []
    step = jax.jit(lambda p, c, tok: model.apply(
        p, c, tok, bank, method=CaptionModel.decode_step))
    for t in range(T):
        carry, logits = step(params, carry, jnp.asarray(prev))
        got.append(np.asarray(logits))
        prev = labels[:, t]
    assert np.asarray(carry.pos).tolist() == [T] * B
    inputs = np.concatenate([np.ones((B, 1), np.int32), labels[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p: ref.forward(
            p, _as_file(cfg), feats, masks, jnp.asarray(inputs),
            lambda x: x))(params))
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-4)


def test_the_selection_prunes_where_it_should_and_nowhere_else(setup):
    """(b) is not vacuous: with the tiny dense length most prefix queries and
    every caption query attend to fewer keys than they see; with the large
    one every query attends to all it sees."""
    cfg, model, params, feats, masks, _labels = setup
    _tok, _score, tally = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=3, return_tally=True))(params)
    seen, took, dense = np.asarray(tally).sum(0)
    if cfg.sparse_dense_len > F + T:
        assert took == seen and dense > 0
    else:
        assert 0.3 * seen < took < 0.9 * seen


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
def test_missing_slots_are_as_if_they_were_not_there(setup, ref):
    """A clip whose missing slots lie anywhere reads as the clip of its valid
    slots in order: in the program and in the reference alike."""
    cfg, model, params, _f, _m, labels = setup
    feats, masks, _ = _batch(seed=5, holes=True)
    order = np.argsort(masks["patch"] == 0, axis=1, kind="stable")
    packed = {"patch": np.take_along_axis(feats["patch"], order[..., None], 1)}
    packed_mask = {"patch": np.take_along_axis(masks["patch"], order, 1)}
    assert (packed_mask["patch"][:, :F // 2] == 1).all()
    apply = jax.jit(model.apply)
    got = np.asarray(apply(params, feats, masks, labels))
    np.testing.assert_array_equal(
        got, np.asarray(apply(params, packed, packed_mask, labels)))
    want = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(got, labels), want, atol=5e-5)


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("chunk", [8, 16, 37, 64])
def test_chunked_linear_scan_equals_the_step_recurrence(chunk, impl):
    """(c): chunks that divide the 37 positions and that do not, rows whose
    last positions do not exist; the kernel (interpret mode) and the scan."""
    rows, P, H, d = 3, 37, 4, 8
    q, k, v = (jax.random.normal(key, (rows, P, H, d))
               for key in jax.random.split(jax.random.key(0), 3))
    slopes = linear.decay_slopes(H, 9, 32)
    n = jnp.array([37, 20, 1])
    state = jnp.zeros((rows, H, d, d), jnp.float32)
    outs = []
    for t in range(P):
        out, moved = linear.linear_attention_step(
            state, q[:, t], k[:, t], v[:, t], slopes)
        state = jnp.where((t < n)[:, None, None, None], moved, state)
        outs.append(out)
    live = (jnp.arange(P)[None] < n[:, None])[:, :, None, None]
    got, last = linear.chunked_linear_attention(q, k, v, slopes, n, chunk, impl)
    np.testing.assert_allclose(np.asarray(got * live),
                               np.asarray(jnp.stack(outs, 1) * live), atol=5e-5)
    np.testing.assert_allclose(np.asarray(last), np.asarray(state), atol=5e-5)


def test_linear_slopes_are_the_family_s_scaled_by_the_published_depth():
    s = np.asarray(linear.decay_slopes(32, 8, 32))
    np.testing.assert_allclose(s[0], 2 ** (-8 / 32) * (1 - 8 / 31 + 1e-5), rtol=1e-6)
    np.testing.assert_allclose(s[-1], 2 ** -8 * (1 - 8 / 31 + 1e-5), rtol=1e-6)
    assert (np.diff(s) < 0).all()
    assert np.asarray(linear.decay_slopes(32, 15, 32))[0] < s[0]   # deeper: slower


def _sparse_case(P=40, rows=2, H=4, G=2, d=8, seed=0):
    q, k, v = (jax.random.normal(key, shape) for key, shape in zip(
        jax.random.split(jax.random.key(seed), 3),
        [(rows, P, H, d), (rows, P, G, d), (rows, P, G, d)]))
    spec = sparse.SparseSpec(kernel=4, stride=2, block=4, topk=2, window=6,
                             init_blocks=1, dense_len=12)
    return q, k, v, sparse.compress_keys(k, spec), jnp.array([40, 27]), spec


@pytest.mark.parametrize("tiles", [(8, 8), (8, 16), (16, 8), (40, 40)])
def test_sparse_flash_kernel_equals_the_walk_over_query_blocks(tiles):
    q, k, v, ck, n, spec = _sparse_case()
    want, tally = sparse.sparse_prefill(q, k, v, ck, n, spec, "xla", q_block=16)
    got, tally2 = sparse.sparse_prefill(q, k, v, ck, n, spec, "pallas",
                                        q_block=8, tiles=tiles)
    live = (jnp.arange(40)[None] < n[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(got * live), np.asarray(want * live),
                               atol=2e-6)
    np.testing.assert_array_equal(np.asarray(tally), np.asarray(tally2))


def test_attended_count_counts_the_mask_it_never_forms():
    q, _k, _v, ck, n, spec = _sparse_case()
    P = q.shape[1]
    q_pos = jnp.broadcast_to(jnp.arange(P), (2, P))
    chosen, dense = sparse.select_blocks(q, ck, n, q_pos, P, spec)
    mask = sparse.visible_keys(chosen, q_pos, n, P, spec)
    np.testing.assert_array_equal(
        np.asarray(sparse.attended_count(chosen, q_pos, n, spec)),
        np.asarray(mask.sum(-1)))
    assert np.asarray(dense)[:, :11].all() and not np.asarray(dense)[:, 11:].any()
    # a sparse query takes at most topk + init blocks and its window
    took = np.asarray(mask.sum(-1))[0, :, 20:]
    assert took.max() <= (2 + 1) * 4 + 6 and took.min() >= 6


def test_compressed_keys_are_window_means():
    _q, k, _v, ck, _n, spec = _sparse_case()
    assert ck.shape == (2, (40 - 4) // 2 + 1, 2, 8)
    np.testing.assert_allclose(np.asarray(ck[:, 3]),
                               np.asarray(k[:, 6:10].mean(1)), atol=1e-6)


def test_beam_with_the_prefix_held_once_emits_what_a_copy_a_lane_does(setup, ref):
    """(d): "lanes" closes over the encoder output (one copy of a clip's
    prefix keys), "reference" tiles it a lane; both emit the reference's own
    search's captions."""
    cfg, model, params, feats, masks, _labels = setup
    out = {impl: jax.jit(lambda p, impl=impl: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl,
        return_tally=True))(params) for impl in ("lanes", "reference")}
    for a, b in zip(out["lanes"], out["reference"]):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    tokens, score = jax.jit(lambda p: ref.beam_search(
        p, _as_file(cfg), feats, masks, 5, T))(params)
    np.testing.assert_array_equal(np.asarray(out["lanes"][0]), np.asarray(tokens))
    np.testing.assert_allclose(np.asarray(out["lanes"][1]), np.asarray(score),
                               atol=1e-4)


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
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
    with open(os.path.join(ROOT, "tests", "golden_beam_pr37.json")) as f:
        return json.load(f)


_YARN = (("beta_fast", 1), ("beta_slow", 1), ("factor", 32), ("mscale", 1),
         ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096))
_OTHER_KINDS = {
    "lstm": dict(vocab_size=64, modalities=(("resnet", 32), ("c3d", 16)),
                 d_embed=32, d_hidden=32, d_att=16, encoder="temporal_attention",
                 max_len=12, max_frames=8, dtype="float32", dropout=0.0),
    "latent_moe": dict(
        decoder="latent_moe", vocab_size=64,
        modalities=(("resnet", 32), ("c3d", 16)), max_len=12, max_frames=8,
        dtype="float32", param_dtype="float32", hidden_size=32,
        num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=48,
        moe_intermediate_size=16, n_routed_experts=16, n_shared_experts=1,
        num_experts_per_tok=4, routed_scaling_factor=2.827,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, rms_norm_eps=1e-6,
        rope_theta=50000.0, rope_scaling=_YARN, experts_held=4,
        expert_share_index=0, initializer_range=0.3),
}


def assert_the_golden_scores(kind: str, score, bits: list) -> None:
    """A kind's beam scores against a golden's bits: to the bit, but for the
    routed-expert kinds, whose held experts are one grouped product a layer
    since PR 51 (models/experts.py): a token's experts are summed in the
    order it chose them, no longer in the experts' own, so their scores sit
    within float32's last bits of the goldens written before it
    (tests/test_window_moe.py holds them to the bit as that commit gave
    them)."""
    got = np.asarray(score, np.float32)
    if kind in ("latent_moe", "window_moe"):
        np.testing.assert_allclose(
            got, np.asarray(bits, np.uint32).view(np.float32), rtol=2e-6)
    else:
        assert got.view(np.uint32).tolist() == bits


@pytest.mark.parametrize("impl", ["lanes", "reference"])
@pytest.mark.parametrize("kind", sorted(_OTHER_KINDS))
def test_the_other_decoders_beam_outputs_are_the_parent_commit_s(kind, impl):
    """(e): tokens and score bits of beam 5 on seeded weights, as the commit
    before this decoder kind emitted them (tests/golden_beam_pr37.json,
    written by running these lines on that commit)."""
    model = CaptionModel(ModelConfig(**_OTHER_KINDS[kind]))
    rng = np.random.default_rng(1)
    n = rng.integers(4, 9, size=6)
    mask = (np.arange(8)[None] < n[:, None]).astype(np.float32)
    feats = {name: (rng.normal(size=(6, 8, dim)) * mask[..., None]
                    ).astype(np.float32) for name, dim in (("resnet", 32), ("c3d", 16))}
    masks = {name: mask.copy() for name in ("resnet", "c3d")}
    labels = rng.integers(4, 64, size=(6, 12)).astype(np.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    tokens, score = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl)[:2])(params)
    want = _golden()[f"{kind}.{impl}"]
    assert np.asarray(tokens).tolist() == want["tokens"]
    assert_the_golden_scores(kind, score, want["score_bits"])


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
def test_the_lstm_only_entry_points_say_so(setup):
    _cfg, model, params, feats, masks, labels = setup
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    with pytest.raises(NotImplementedError, match="sparse_linear"):
        model.apply(params, enc, labels, method=CaptionModel.teacher_force_logps)
    with pytest.raises(ValueError, match="rl.enabled"):
        get_preset("minicpm_sala_8l_xe").override(rl__enabled=True)
    with pytest.raises(ValueError, match="mixer_types"):
        bad = ModelConfig(**{**TINY, "mixer_types": ("minicpm4",)})
        CaptionModel(bad).init(jax.random.key(0), feats, masks, labels)


def test_the_preset_holds_the_published_widths():
    mc = get_preset("minicpm_sala_8l_eval_beam5").model
    assert (mc.hidden_size, mc.intermediate_size, mc.vocab_size) == (4096, 16384, 73448)
    assert mc.mixer_types == ("minicpm4",) + ("lightning-attn",) * 6 + ("minicpm4",)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) == (32, 2, 128)
    model = CaptionModel(mc)
    feats = {"patch": jnp.zeros((1, 8, 1024))}
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), feats, {"patch": jnp.ones((1, 8))},
        jnp.zeros((1, 30), jnp.int32)))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    assert count == 2_824_763_392
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))


def test_flops_dispatch_on_the_decoder_kind():
    sala = get_preset("minicpm_sala_8l_xe").model
    short = flops.sparse_linear_per_tok_flops(sala, context=100)
    long = flops.sparse_linear_per_tok_flops(sala, context=16000)
    # 2 x (2 sparse layers of 254 M + 6 linear of 285 M) and the mixers
    assert 4.4e9 < short < 4.6e9 and short < long < 1.05 * short
    assert flops.model_xe_flops_per_row(sala) > 3 * 16384 * short


# ---- the seams: Evaluator, checkpoints, cli/eval.py ---------------------------


def _tiny_overrides():
    return {"model__" + k: v for k, v in TINY.items() if k != "decoder"}


def _dataset(tmp_path, videos: int):
    from cst_captioning_tpu.data.dataset import CaptionDataset
    from cst_captioning_tpu.data.synthetic import make_synthetic_dataset

    paths = make_synthetic_dataset(
        str(tmp_path / "data"), num_videos=videos, vocab_words=40,
        modalities=dict(TINY["modalities"]), max_frames=F, splits=(1.0, 0.0),
        seed=3)
    return CaptionDataset(paths["info_json"], {"patch": paths["patch"]},
                          "train", F), paths


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
def test_evaluator_tells_the_kinds_of_state_apart_and_counts_the_keys(
        tmp_path, setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator

    cfg, model, params, *_ = setup
    ds, _paths = _dataset(tmp_path, 12)
    base = dataclasses.replace(
        get_preset("minicpm_sala_8l_eval_beam5").eval, max_len=T,
        metrics=("CIDEr-D",), split="train")
    assert base.prefill_program and base.beam_impl == "lanes"
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
    # 4 clips: prefix keys and values once a clip, caption keys a lane
    kv = 2 * 2 * (4 * F + 4 * 5 * T) * 2 * 8 * 4
    assert g["decode.kv_bytes"] == kv
    assert g["decode.index_bytes"] == 2 * 4 * ((F - 4) // 2 + 1) * 2 * 8 * 4
    assert g["decode.state_bytes"] == 2 * 4 * 5 * 4 * 8 * 8 * 4
    assert g["decode.cache_bytes"] == sum(
        g[k] for k in ("decode.kv_bytes", "decode.index_bytes", "decode.state_bytes"))
    # copied a lane, the prefix's part is five times as large
    assert snap_tiled["gauges"]["decode.kv_bytes"] == \
        2 * 2 * (4 * 5 * F + 4 * 5 * T) * 2 * 8 * 4
    assert 0 < c["sparse.keys_selected"] <= c["sparse.keys_visible"]
    assert c["sparse.dense_fallback_queries"] > 0
    spans = [json.loads(line) for line in open(tmp_path / "obs" / "events.jsonl")]
    names = [e["name"] for e in spans if e.get("event") == "span"]
    assert names.count("eval.prefill") == names.count("eval.decode") == 3


@pytest.mark.parametrize("setup", ["prunes"], indirect=True)
def test_prefill_program_is_a_beam_search_on_one_device(setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator

    _cfg, model, *_ = setup
    ecfg = dataclasses.replace(get_preset("minicpm_sala_8l_eval_beam5").eval,
                               beam_size=1)
    with pytest.raises(ValueError, match="prefill_program"):
        Evaluator(model, None, ecfg, batch_size=4)


def test_cli_eval_runs_the_eval_preset_end_to_end(tmp_path, capsys):
    """``cli/eval.py`` on the configuration's eval preset (tiny overrides):
    a checkpoint of seeded weights saved by the ``Trainer`` of its XE preset
    (``train_xe(epochs=0)`` is a no-op), loaded and decoded at beam 5. No
    entry point of its own."""
    from cst_captioning_tpu.cli import eval as cli_eval
    from cst_captioning_tpu.train.trainer import Trainer

    over = _tiny_overrides()
    ds, paths = _dataset(tmp_path, 6)
    cfg = get_preset("minicpm_sala_8l_xe").override(
        **over, data__batch_size=2, train__ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(cfg, ds, None, use_mesh=False)
    assert trainer.train_xe(epochs=0) is None
    trainer.ckpt.save(jax.device_get(trainer.state), None)
    trainer.close()
    ds.close()
    args = ["--preset", "minicpm_sala_8l_eval_beam5",
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
