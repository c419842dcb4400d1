"""The fifth decoder kind (``ModelConfig.decoder = "window_moe"``,
models/window_moe.py; ops/window_attention.py; models/experts.py) against its
plain reference (benchmark/reference_window_moe.py), at a small size on seeded
random weights, float32 stated: teacher forcing (one forward over prefix and
caption) and prefill then single steps through the cache, with windows of 8
that roll over prefix and caption, one window that holds everything, and the
published window of 128 behind clips of 127, 128 and 129 slots; four controls
that must fail the comparison (the sink left out, the value scale left out,
one rope base for both kinds, a full layer's key/value heads in a window
layer); both flash kernels (interpret mode) against the walk over query
blocks; the step for all lanes at once against the vmapped one and against
the prefix copied a lane, and against the reference's own search; the shares
of an expert layer adding up to the uncut layer; the expert layer's move out
of ``latent_moe`` changing no bit of it. Then the seams: the ``Evaluator``'s
gauges and counters, ``cli/eval.py`` on the configuration's eval preset,
``obs/flops.py``, ``cli.obs_report``'s table.
"""

import dataclasses
import hashlib
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
from cst_captioning_tpu.models import CaptionModel, captioner, experts
from cst_captioning_tpu.models.captioner import EncoderOutput
from cst_captioning_tpu.obs import flops
from cst_captioning_tpu.ops import window_attention as wa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T = 12
TINY = dict(
    decoder="window_moe", vocab_size=32, modalities=(("patch", 16),), max_len=T,
    max_frames=48, dtype="float32", param_dtype="float32", hidden_size=32,
    num_hidden_layers=4, first_k_dense_replace=1, intermediate_size=48,
    moe_intermediate_size=16, n_routed_experts=16, n_shared_experts=0,
    num_experts_per_tok=4, routed_scaling_factor=1.0, num_attention_heads=8,
    num_key_value_heads=2, swa_num_key_value_heads=4, head_dim=12, v_head_dim=8,
    sliding_window=8, partial_rotary_factor=0.334, rope_theta=1e7,
    swa_rope_theta=1e4, attention_value_scale=0.707, rms_norm_eps=1e-5,
    initializer_range=0.3, experts_held=4, expert_share_index=1,
    mixer_types=("full", "window", "window", "full"), published_layers=48,
    first_layer_index=0)
# windows of 8 roll over prefix and caption; one window of 64 holds both whole
# (a window layer is then full attention plus the sink); the published 128
# behind clips of 127, 128 and 129 slots, a whole prefix and a short one
SETUPS = {
    "rolls": ({}, [35, 36, 40, 48, 7, 8, 9]),
    "one_window": ({"sliding_window": 64}, [35, 36, 40, 48, 7, 8, 9]),
    "published_window": ({"sliding_window": 128, "max_frames": 144},
                         [127, 128, 129, 144, 16, 130]),
}


@pytest.fixture(scope="module")
def ref():
    """The reference, loaded from its file as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        "reference_window_moe",
        os.path.join(ROOT, "benchmark", "reference_window_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _as_file(cfg: ModelConfig) -> dict:
    return json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))


def _batch(n, frames: int, seed=1, holes=False):
    n = np.asarray(n)
    rng = np.random.default_rng(seed)
    mask = (np.arange(frames)[None] < n[:, None]).astype(np.float32)
    if holes:
        mask = np.stack([rng.permutation(row) for row in mask])
    feats = {"patch": rng.normal(size=(len(n), frames, 16)).astype(np.float32)}
    labels = rng.integers(4, TINY["vocab_size"], size=(len(n), T)).astype(np.int32)
    return feats, {"patch": mask}, labels


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """The prefix's dense-FFN row blocks at a scale the tiny model crosses."""
    from cst_captioning_tpu.models import window_moe

    patch = pytest.MonkeyPatch()
    patch.setattr(window_moe, "FFN_ROWS", 64)
    yield
    patch.undo()


@pytest.fixture(scope="module", params=sorted(SETUPS))
def setup(request):
    over, n = SETUPS[request.param]
    cfg = ModelConfig(**{**TINY, **over})
    model = CaptionModel(cfg)
    feats, masks, labels = _batch(n, cfg.max_frames)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def _inputs(labels):
    return np.concatenate(
        [np.ones((len(labels), 1), np.int32), labels[:, :-1]], axis=1)


def _reference_logits(ref, params, model: dict, feats, masks, labels):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p: ref.forward(
            p, model, feats, masks, jnp.asarray(_inputs(labels)),
            lambda x: x))(params))


def _picked(logits, labels):
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    return np.take_along_axis(np.asarray(logp), labels[..., None], -1)[..., 0]


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_init_declares_every_parameter_without_a_forward(setup):
    _cfg, _model, params, *_ = setup
    dec = params["params"]["decoder"]
    assert set(dec) == {"embed_patch", "embed_tokens", "norm", "lm_head",
                        "layers_0", "layers_1", "layers_2", "layers_3"}
    # layer 0: full attention (2 key/value heads), the dense FFN, no sink
    assert set(dec["layers_0"]) == {
        "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
        "post_attention_layernorm", "gate_proj", "up_proj", "down_proj"}
    assert dec["layers_0"]["k_proj"].shape == (32, 2 * 12)
    assert dec["layers_0"]["v_proj"].shape == (32, 2 * 8)
    # layer 1: window attention (4 key/value heads, a sink a query head), the
    # held experts and the router over all 16; no shared expert is declared
    assert set(dec["layers_1"]) == {
        "input_layernorm", "q_proj", "k_proj", "v_proj", "o_proj",
        "post_attention_layernorm", "attention_sink_bias", "gate",
        "e_score_correction_bias", "experts_gate_proj", "experts_up_proj",
        "experts_down_proj"}
    assert dec["layers_1"]["k_proj"].shape == (32, 4 * 12)
    assert dec["layers_1"]["q_proj"].shape == (32, 8 * 12)
    assert dec["layers_1"]["o_proj"].shape == (8 * 8, 32)
    assert dec["layers_1"]["attention_sink_bias"].shape == (8,)
    assert np.asarray(dec["layers_1"]["attention_sink_bias"]).all()   # drawn
    assert dec["layers_1"]["experts_up_proj"].shape == (4, 32, 16)
    assert dec["layers_1"]["gate"].shape == (32, 16)
    assert "attention_sink_bias" not in dec["layers_3"]


def test_teacher_forced_logits_match_the_reference(setup, ref):
    """``__call__`` (one forward over each clip's prefix and its caption
    behind it) against the reference's, logits compared: float32 on both
    sides, so what is left is summation order (a logit reads up to 7; 1e-5
    is the largest difference seen)."""
    cfg, model, params, feats, masks, labels = setup
    logits = jax.jit(model.apply)(params, feats, masks, labels)
    assert logits.shape == (len(labels), T, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    want = _reference_logits(ref, params, _as_file(cfg), feats, masks, labels)
    assert np.abs(want).max() > 3.0         # a peaked distribution, not noise
    np.testing.assert_allclose(np.asarray(logits), want, atol=1e-4)
    picked = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(logits, labels), picked, atol=5e-5)


def test_prefill_then_one_token_at_a_time_through_the_cache_matches_the_full_forward(
        setup, ref):
    """T single steps on the carry (a full layer over the clip's whole prefix
    keys, a window layer over the clip's tail slice, both over the lane's own
    keys that grow) against the reference's full forward, which has no cache;
    what each step counted is the pairs at its position."""
    cfg, model, params, feats, masks, labels = setup
    B, F, W = len(labels), cfg.max_frames, cfg.sliding_window
    n = masks["patch"].sum(1).astype(int)
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    keys, values = enc.memory
    tail = min(W, F)
    # once a clip, head-major: a full layer's whole prefix, a window layer's
    # last window; the window-bounded leaf is no longer than the window
    assert [k.shape for k in keys] == [
        (B, 2, F, 12), (B, 4, tail, 12), (B, 4, tail, 12), (B, 2, F, 12)]
    assert [v.shape for v in values] == [
        (B, 2, F, 8), (B, 4, tail, 8), (B, 4, tail, 8), (B, 2, F, 8)]
    assert np.asarray(enc.memory_proj).tolist() == \
        np.clip(n - W, 0, F - tail).tolist()
    # a lane's own: max_len positions a layer
    assert [k.shape for k in enc.carry.k] == [
        (B, 2, T, 12), (B, 4, T, 12), (B, 4, T, 12), (B, 2, T, 12)]
    assert all(x.shape[0] == B for x in jax.tree.leaves(enc.carry))
    assert enc.carry.routed.shape == (B, 3, 5)
    # the prefix's pairs: min(i + 1, W) and i + 1 summed over a clip's slots
    i = np.arange(F)[None]
    live = i < n[:, None]
    assert np.asarray(enc.carry.counted)[:, 0].tolist() == np.stack(
        [(np.minimum(i + 1, W) * live).sum(1), ((i + 1) * live).sum(1)], 1).tolist()
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    step = jax.jit(lambda p, c, tok: model.apply(
        p, c, tok, bank, method=CaptionModel.decode_step))
    carry, got, counted = enc.carry, [], []
    for tokens in _inputs(labels).T:
        carry, logits = step(params, carry, jnp.asarray(tokens))
        got.append(np.asarray(logits))
        counted.append(np.asarray(carry.counted)[:, 0])
    assert np.asarray(carry.pos).tolist() == [T] * B
    want = _reference_logits(ref, params, _as_file(cfg), feats, masks, labels)
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-4)
    pos = n[:, None] + np.arange(T)[None]
    counted = np.stack(counted, 1)
    assert (counted[..., 0] == np.minimum(pos + 1, W)).all()
    assert (counted[..., 1] == pos + 1).all()
    # the held experts' tallies: a row's assignments on all experts are its
    # top 4, a layer; on the held four at most that
    routed = np.asarray(carry.routed)
    assert (routed[..., -1] == 4).all() and (routed[..., :-1].sum(-1) <= 4).all()


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_missing_slots_are_as_if_they_were_not_there(setup, ref):
    cfg, model, params, _f, _m, labels = setup
    feats, masks, _ = _batch(SETUPS["rolls"][1], cfg.max_frames, seed=5, holes=True)
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


# ---- the controls: each must FAIL the comparison the tests above pass ----------


def _narrow_window_heads(params, model: dict):
    """The window layers read through a full layer's number of key/value
    heads: their first ``num_key_value_heads`` heads' columns of k and v."""
    G, dk, dv = model["num_key_value_heads"], model["head_dim"], model["v_head_dim"]
    dec = dict(params["params"]["decoder"])
    for i, kind in enumerate(model["mixer_types"]):
        if kind == "window":
            layer = dict(dec[f"layers_{i}"])
            layer["k_proj"] = layer["k_proj"][:, :G * dk]
            layer["v_proj"] = layer["v_proj"][:, :G * dv]
            dec[f"layers_{i}"] = layer
    return {"params": {"decoder": dec}}, dict(model, swa_num_key_value_heads=G)


def _no_sink(params, model: dict):
    dec = dict(params["params"]["decoder"])
    for i, kind in enumerate(model["mixer_types"]):
        if kind == "window":
            layer = dict(dec[f"layers_{i}"])
            layer.pop("attention_sink_bias")
            dec[f"layers_{i}"] = layer
    return {"params": {"decoder": dec}}, model


CONTROLS = {
    "the_sink_left_out": _no_sink,
    "the_value_scale_left_out":
        lambda p, m: (p, dict(m, attention_value_scale=1.0)),
    "one_rope_base_for_both_kinds":
        lambda p, m: (p, dict(m, swa_rope_theta=m["rope_theta"])),
    "a_full_layers_key_value_heads_in_a_window_layer": _narrow_window_heads,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_a_departure_from_the_equations_fails_the_comparison(setup, ref, control):
    """The reference with one of the layer's rules altered, in the sound
    reference's place: the program's logits, which sit within 1e-4 of the
    sound reference's, leave the altered one's by hundreds of times that."""
    cfg, model, params, feats, masks, labels = setup
    got = np.asarray(jax.jit(model.apply)(params, feats, masks, labels))
    altered_params, altered = CONTROLS[control](params, _as_file(cfg))
    other = _reference_logits(ref, altered_params, altered, feats, masks, labels)
    assert np.abs(got - other).max() > 2e-2
    assert np.abs(_picked(got, labels) - _picked(other, labels)).mean() > 2e-3


# ---- the two kernels ------------------------------------------------------------


def _attention_case(P=96, rows=2, H=8, G=2, dk=24, dv=16, seed=0):
    keys = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(keys[0], (rows, P, H, dk))
    k = jax.random.normal(keys[1], (rows, P, G, dk))
    v = jax.random.normal(keys[2], (rows, P, G, dv))
    return q, k, v, 0.5 * jax.random.normal(keys[3], (H,)), jnp.array([P, 70])


def _dense(q, k, v, sink, window):
    """The definition over one ``[positions, positions]`` product (the only
    such array in this file)."""
    P, H, G = q.shape[1], q.shape[2], k.shape[2]
    kk, vv = (jnp.repeat(x, H // G, axis=2) for x in (k, v))
    s = jnp.einsum("bihd,bjhd->bhij", q, kk) / np.sqrt(q.shape[-1])
    i, j = jnp.arange(P)[:, None], jnp.arange(P)[None]
    ok = (j <= i) & (j > i - window if window else True)
    e = jnp.where(ok, jnp.exp(jnp.where(ok, s, -jnp.inf)), 0.0)
    below = e.sum(-1, keepdims=True)
    if sink is not None:
        below = below + jnp.exp(sink)[None, :, None, None]
    return jnp.einsum("bhij,bjhd->bihd", e / below, vv)


@pytest.mark.parametrize("window", [8, 13, 96, 200])
def test_the_walk_over_query_blocks_is_the_band_and_the_sink(window):
    """``impl="xla"`` against the definition; a window that holds every
    position (96, 200) is full attention plus the sink, and without the sink
    :func:`full_prefill`."""
    q, k, v, sink, n = _attention_case()
    got = wa.window_prefill(q, k, v, sink, n, window, impl="xla")
    np.testing.assert_allclose(np.asarray(got), np.asarray(
        _dense(q, k, v, sink, window)), atol=2e-6)
    if window >= 96:
        np.testing.assert_allclose(np.asarray(got), np.asarray(
            _dense(q, k, v, sink, None)), atol=2e-6)
        np.testing.assert_allclose(
            np.asarray(wa.full_prefill(q, k, v, n, impl="xla")),
            np.asarray(_dense(q, k, v, None, None)), atol=2e-6)
        # the sink takes probability: without it the outputs are larger
        assert np.abs(np.asarray(got) - np.asarray(
            _dense(q, k, v, None, None))).max() > 1e-2


@pytest.mark.parametrize("tiles", [(16, 8), (8, 16), (32, 32), (16, 16)])
@pytest.mark.parametrize("window", [8, 13, 40, None])
def test_flash_kernel_equals_the_walk_over_query_blocks(window, tiles):
    """The kernels (interpret mode) on 96 positions, 4 query heads a
    key/value head, keys wider than values, rows whose last positions do not
    exist; the rows under ``n`` are compared, the others are nobody's. Bands
    narrower than a tile, wider than one and cut by tile edges, and the
    causal walk (``None``: the full kernel)."""
    q, k, v, sink, n = _attention_case()
    if window is None:
        want = wa.full_prefill(q, k, v, n, impl="xla")
        got = wa.full_prefill(q, k, v, n, impl="pallas", tiles=tiles)
    else:
        want = wa.window_prefill(q, k, v, sink, n, window, impl="xla")
        got = wa.window_prefill(q, k, v, sink, n, window, impl="pallas",
                                tiles=tiles)
    live = (jnp.arange(96)[None] < n[:, None])[:, :, None, None]
    np.testing.assert_allclose(np.asarray(got * live), np.asarray(want * live),
                               atol=2e-6)


def test_a_query_tile_walks_only_the_key_tiles_its_band_reaches():
    """The grid's last axis is the longest walk: three key tiles of 128 for a
    query tile of 256 under the published window, every tile of the diagonal
    for the full kernel; tiles that do not divide each other are refused."""
    first, last, steps = wa._walk(16384, 256, 128, 128)
    assert steps == 3 and (int(first(5)), int(last(5))) == (9, 11)
    assert (int(first(0)), int(last(0))) == (0, 1)
    _first, last, steps = wa._walk(16384, 128, 512, None)
    assert steps == 32 and int(last(127)) == 31
    q, k, v, sink, n = _attention_case()
    with pytest.raises(ValueError, match="divide"):
        wa.window_prefill(q, k, v, sink, n, 8, impl="pallas", tiles=(24, 16))


# ---- the beam ----------------------------------------------------------------------


def _search(model, params, feats, masks, beam, impl="lanes"):
    return jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=beam, beam_impl=impl,
        return_tally=True))(params)


@pytest.mark.parametrize("beam", [1, 3, 5])
@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_the_step_for_all_lanes_is_the_vmapped_step_bit_for_bit(
        setup, beam, monkeypatch):
    """``lane_decode_step`` calls this kind's step with all lanes at once;
    vmapped a lane like the other kinds' it emits the same tokens and counts
    to the bit, and the same scores to float32's last bits: a lane's grouped
    product (models/experts.py) is then one of a batch of products, which the
    CPU's kernels sum in another order than the one product of all lanes."""
    _cfg, model, params, feats, masks, _labels = setup
    lanes = _search(model, params, feats, masks, beam)
    monkeypatch.setattr(captioner, "ALL_LANES", ())
    vmapped = _search(model, params, feats, masks, beam)
    np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(vmapped[0]))
    np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(vmapped[1]),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(lanes[2]), jax.tree.leaves(vmapped[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("beam", [1, 3, 5])
@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_beam_with_the_prefix_held_once_emits_what_a_copy_a_lane_does(
        setup, ref, beam):
    """"lanes" closes over the encoder output (one copy of a clip's prefix
    keys), "reference" tiles it a lane and runs the one-lane step over the
    flattened rows: the same tokens and counts; the scores agree to float32's
    last bits and not bit for bit, because a clip's shared keys meet ``lanes
    x heads`` query rows in one product when the beams are lanes and
    ``heads`` rows when they are rows of the batch, and the CPU's kernels sum
    the 12 terms in another order (the EVA and sparse/linear kinds' tests say
    the same of theirs). At beam 5 both emit the reference's own search's
    captions."""
    cfg, model, params, feats, masks, _labels = setup
    lanes = _search(model, params, feats, masks, beam)
    tiled = _search(model, params, feats, masks, beam, impl="reference")
    np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(tiled[0]))
    np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(tiled[1]),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(lanes[2]), jax.tree.leaves(tiled[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    routed, pairs = lanes[2]
    assert routed.shape == (3, 5) and pairs.shape == (1, 2)
    if beam == 5:
        tokens, score = jax.jit(lambda p: ref.beam_search(
            p, _as_file(cfg), feats, masks, 5, T))(params)
        np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(tokens))
        np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(score),
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


def _golden(name: str):
    with open(os.path.join(ROOT, "tests", name)) as f:
        return json.load(f)


def _six_clips(kind_sizes: dict):
    """The six clips of two modalities the goldens were written on."""
    model = CaptionModel(ModelConfig(**kind_sizes))
    rng = np.random.default_rng(1)
    n = rng.integers(4, 9, size=6)
    mask = (np.arange(8)[None] < n[:, None]).astype(np.float32)
    feats = {name: (rng.normal(size=(6, 8, dim)) * mask[..., None]
                    ).astype(np.float32) for name, dim in (("resnet", 32), ("c3d", 16))}
    masks = {name: mask.copy() for name in ("resnet", "c3d")}
    labels = rng.integers(4, 64, size=(6, 12)).astype(np.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return model, params, feats, masks, labels


def _golden_kinds():
    from test_eva import TINY as EVA
    from test_sparse_linear import _OTHER_KINDS

    two = {"modalities": (("resnet", 32), ("c3d", 16)), "max_len": 12,
           "max_frames": 8, "vocab_size": 64}
    return {"latent_moe": _OTHER_KINDS["latent_moe"], "eva": {**EVA, **two},
            "window_moe": {**TINY, **two}}


@pytest.mark.parametrize("impl", ["lanes", "reference"])
@pytest.mark.parametrize("kind", ["eva", "latent_moe", "window_moe"])
def test_beam_outputs_are_the_golden_s(kind, impl):
    """Tokens and score bits of beam 5 on seeded weights: the EVA and the
    latent/expert kinds' as the commit before this decoder kind emitted them
    (the other kinds' step programs did not change; the expert layer moved
    out of ``latent_moe`` and changed no bit), this kind's as this commit
    does (tests/golden_beam_pr45.json, written by running these lines on the
    parent commit and, for ``window_moe``, on this one; test_eva.py holds the
    LSTM and the sparse/linear kind to tests/golden_beam_pr41.json). Since
    PR 51 the two routed-expert kinds' tokens are those and their scores
    float32's last bits from those (``assert_the_golden_scores``)."""
    from test_sparse_linear import assert_the_golden_scores

    model, params, feats, masks, _labels = _six_clips(_golden_kinds()[kind])
    tokens, score = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl)[:2])(params)
    want = _golden("golden_beam_pr45.json")[f"{kind}.{impl}"]
    assert np.asarray(tokens).tolist() == want["tokens"]
    assert_the_golden_scores(kind, score, want["score_bits"])
    # the routed-expert kinds' bits as PR 51's grouped product gives them
    # (tests/golden_beam_pr51.json, written by running these lines on it)
    now = _golden("golden_beam_pr51.json").get(f"{kind}.{impl}", want)
    assert np.asarray(score, np.float32).view(np.uint32).tolist() == now["score_bits"]


def test_latent_moe_is_bit_identical_after_its_expert_layer_moved():
    """Teacher forcing (the grouped product in its differentiable spelling)
    and prefill then twelve steps, as PR 51's commit computed them: every bit
    of the logits (a hash) and the steps' tallies
    (tests/golden_beam_pr51.json); the first logits within float32's last
    bits of what the commit before the expert layer moved gave, and the
    tallies the same (tests/golden_beam_pr45.json)."""
    model, params, feats, masks, labels = _six_clips(_golden_kinds()["latent_moe"])
    was, want = _golden("golden_beam_pr45.json"), _golden("golden_beam_pr51.json")
    logits = np.asarray(jax.jit(model.apply)(params, feats, masks, labels), np.float32)
    assert list(logits.shape) == want["latent_moe.call"]["shape"] == \
        was["latent_moe.call"]["shape"]
    np.testing.assert_allclose(
        logits[0, 0, :8], np.asarray(was["latent_moe.call"]["first_row_bits"],
                                     np.uint32).view(np.float32), rtol=1e-5)
    assert logits[0, 0, :8].view(np.uint32).tolist() == \
        want["latent_moe.call"]["first_row_bits"]
    assert hashlib.sha256(logits.tobytes()).hexdigest() == \
        want["latent_moe.call"]["sha256"]

    def through_the_cache(p):
        enc = model.apply(p, feats, masks, method=CaptionModel.encode)
        bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
        carry, outs = enc.carry, []
        for tokens in _inputs(labels).T:
            carry, out = model.apply(p, carry, jnp.asarray(tokens), bank,
                                     method=CaptionModel.decode_step)
            outs.append(out)
        return jnp.stack(outs, 1), carry.routed

    logits, routed = jax.jit(through_the_cache)(params)
    assert hashlib.sha256(np.asarray(logits, np.float32).tobytes()).hexdigest() \
        == want["latent_moe.steps"]["sha256"]
    assert np.asarray(routed).tolist() == want["latent_moe.steps"]["routed"] == \
        was["latent_moe.steps"]["routed"]


# ---- the chip's share ------------------------------------------------------------


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref):
    """Four shares of four experts each (what four chips would hold of a
    16-expert layer), each routing over all 16 and normalising over all 4
    chosen, computed by the program's layer and by the reference's given the
    same share: a share's program part is its reference part, and the four
    parts sum to the uncut reference's layer. Nothing is computed on every
    chip alike here (no shared expert), so nothing is counted once."""
    rng = np.random.default_rng(0)
    h, m, E, k, held = 32, 16, 16, 4, 4
    x = jnp.asarray(rng.normal(size=(40, h)), jnp.float32)
    whole = {
        "gate": jnp.asarray(rng.normal(size=(h, E)) * 0.3, jnp.float32),
        "e_score_correction_bias": jnp.asarray(rng.normal(size=(E,)) * 0.3, jnp.float32),
        "experts_gate_proj": jnp.asarray(rng.normal(size=(E, h, m)) * 0.3, jnp.float32),
        "experts_up_proj": jnp.asarray(rng.normal(size=(E, h, m)) * 0.3, jnp.float32),
        "experts_down_proj": jnp.asarray(rng.normal(size=(E, m, h)) * 0.3, jnp.float32),
    }
    sizes = dict(num_experts_per_tok=k, routed_scaling_factor=1.0,
                 n_routed_experts=E, moe_intermediate_size=m, hidden_size=h,
                 n_shared_experts=0)
    same = lambda y: y  # noqa: E731
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.expert_ffn(
            whole, dict(sizes, experts_held=E, expert_share_index=0), x, same))
        total = np.zeros_like(uncut)
        for share in range(E // held):
            cut = lambda a: a[share * held:(share + 1) * held]  # noqa: E731
            p = {**whole, **{name: cut(whole[name]) for name in whole
                             if name.startswith("experts_")}}
            model = dict(sizes, experts_held=held, expert_share_index=share)
            part = np.asarray(ref.expert_ffn(p, model, x, same))
            cfg = ModelConfig(**{**TINY, "expert_share_index": share})
            got, tally = experts.expert_ffn(
                cfg, p, p["e_score_correction_bias"], x,
                jnp.ones((40,), bool), differentiable=False)
            np.testing.assert_allclose(np.asarray(got), part, atol=2e-5)
            assert int(np.asarray(tally)[:, -1].sum()) == 40 * k
            total += part
    assert np.abs(uncut).max() > 1.0
    np.testing.assert_allclose(total, uncut, atol=2e-5)


# ---- the seams -------------------------------------------------------------------


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_the_lstm_only_entry_points_say_so(setup):
    from cst_captioning_tpu.serving.engine import CaptionService

    cfg, model, params, feats, masks, labels = setup
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    with pytest.raises(NotImplementedError, match="window_moe"):
        model.apply(params, enc, labels, method=CaptionModel.teacher_force_logps)
    with pytest.raises(ValueError, match="rl.enabled"):
        get_preset("mimo_v2_5_ep16_xe").override(rl__enabled=True)
    with pytest.raises(ValueError, match="mixer_types"):
        bad = ModelConfig(**{**TINY, "mixer_types": ("full", "sliding")})
        CaptionModel(bad).init(jax.random.key(0), feats, masks, labels)
    with pytest.raises(ValueError, match="no share"):
        bad = ModelConfig(**{**TINY, "expert_share_index": 4})
        CaptionModel(bad).init(jax.random.key(0), feats, masks, labels)
    with pytest.raises(ValueError, match="unknown decoder"):
        ModelConfig(**{**TINY, "decoder": "mimo"})
    with pytest.raises(NotImplementedError, match="window_moe"):
        CaptionService(model, params, None)


def test_the_preset_holds_the_published_widths():
    mc = get_preset("mimo_v2_5_ep16_eval_beam5").model
    assert (mc.hidden_size, mc.intermediate_size, mc.moe_intermediate_size) == \
        (4096, 16384, 2048)
    assert (mc.num_attention_heads, mc.num_key_value_heads,
            mc.swa_num_key_value_heads, mc.head_dim, mc.v_head_dim) == \
        (64, 4, 8, 192, 128)
    assert (mc.sliding_window, mc.partial_rotary_factor, mc.rope_theta,
            mc.swa_rope_theta, mc.attention_value_scale) == \
        (128, 0.334, 1e7, 1e4, 0.707)
    assert (mc.n_routed_experts, mc.num_experts_per_tok, mc.n_shared_experts,
            mc.experts_held, mc.routed_scaling_factor) == (256, 8, 0, 16, 1.0)
    assert mc.mixer_types == ("full",) + ("window",) * 4 + ("full",) + ("window",) * 5
    from cst_captioning_tpu.models.window_moe import rotary_dims

    assert rotary_dims(mc) == 64
    ev = get_preset("mimo_v2_5_ep16_eval_beam5").eval
    assert (ev.max_len, ev.beam_size, ev.beam_impl, ev.prefill_program) == \
        (30, 5, "lanes", True)
    model = CaptionModel(mc)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"patch": jnp.zeros((1, 8, 1024))},
        {"patch": jnp.ones((1, 8))}, jnp.zeros((1, 30), jnp.int32)))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    # layer 0 (full, dense) 290,463,744; four window and one full expert
    # layer, then five window ones; embedding + head; projector; last norm
    assert count == 290_463_744 + 9 * 498_082_112 + 492_839_168 \
        + 2 * 19072 * 4096 + 1024 * 4096 + 4096
    assert count == 5_426_478_144
    assert all(x.dtype == jnp.bfloat16 or x.ndim == 1
               for x in jax.tree.leaves(shapes))


def test_flops_dispatch_on_the_decoder_kind():
    mc = get_preset("mimo_v2_5_ep16_xe").model
    short = flops.window_moe_per_tok_flops(mc, context=100)
    late = flops.window_moe_per_tok_flops(mc, context=14336)
    # the pairs: 2 full layers grow with the context, 9 window layers stop
    # at 128 keys
    pair = 2 * 64 * (192 + 128)
    assert late - short == pair * (2 * (14336 - 100) + 9 * (128 - 100))
    proj = lambda G: 2 * 4096 * (64 * 192 + G * 320) + 2 * 64 * 128 * 4096  # noqa: E731
    moe = 2 * 4096 * 256 + 2 * 3 * 4096 * 2048 * (8 * 16 / 256)
    assert short == 2 * proj(4) + 9 * proj(8) + 2 * 3 * 4096 * 16384 \
        + 10 * moe + pair * 11 * 100
    assert flops.model_xe_flops_per_row(mc) > 3 * 16384 * short


def _tiny_overrides():
    return {"model__" + k: v for k, v in TINY.items() if k != "decoder"}


def _dataset(tmp_path, videos: int):
    from cst_captioning_tpu.data.dataset import CaptionDataset
    from cst_captioning_tpu.data.synthetic import make_synthetic_dataset

    F = TINY["max_frames"]
    paths = make_synthetic_dataset(
        str(tmp_path / "data"), num_videos=videos, vocab_words=24,
        modalities=dict(TINY["modalities"]), max_frames=F, splits=(1.0, 0.0),
        seed=3)
    return CaptionDataset(paths["info_json"], {"patch": paths["patch"]},
                          "train", F), paths


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_evaluator_tells_the_kinds_of_state_apart_and_counts_pairs_and_experts(
        tmp_path, setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.obs.report import build_report, render_report

    cfg, model, params, *_ = setup
    ds, _paths = _dataset(tmp_path, 12)
    base = dataclasses.replace(
        get_preset("mimo_v2_5_ep16_eval_beam5").eval, max_len=T,
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
    g, c, h = snap["gauges"], snap["counters"], snap["histograms"]
    # 4 clips, float32. The two full layers (2 key/value heads of 12 + 8): a
    # clip's 48 prefix positions once, a lane's 12 caption positions. The two
    # window layers (4 heads): a clip's last 8 positions once, a lane's 12
    full, near = 2 * 2 * 20 * 4, 2 * 4 * 20 * 4
    assert g["decode.prefix_key_bytes"] == full * 4 * 48
    assert g["decode.window_bytes"] == near * (4 * 8 + 4 * 5 * 12)
    assert g["decode.cache_bytes"] == g["decode.prefix_key_bytes"] \
        + g["decode.window_bytes"] + full * 4 * 5 * 12
    # copied a lane, the clip's part is five times as large
    assert snap_tiled["gauges"]["decode.prefix_key_bytes"] == full * 4 * 5 * 48
    assert g["moe.experts_held"] == 4
    # plain causal attention in all four layers against two of them banded
    assert c["attn.pairs_causal"] == 2 * c["attn.pairs_full"]
    assert 0 < c["attn.pairs_window"] < c["attn.pairs_full"]
    assert 0 < c["moe.assignments.local"] < c["moe.assignments"]
    assert h["moe.expert_rows"]["count"] == 3 * (3 * 4)   # batches x layers x held
    events = [json.loads(line) for line in open(tmp_path / "obs" / "events.jsonl")]
    names = [e["name"] for e in events if e.get("event") == "span"]
    assert names.count("eval.prefill") == names.count("eval.decode") == 3
    text = render_report(build_report(events))
    assert "full layers' prefix keys" in text and "plain causal" in text
    assert "routed experts: 4 held" in text


def test_cli_eval_runs_the_eval_preset_end_to_end(tmp_path, capsys):
    """``cli/eval.py`` on the configuration's eval preset (tiny overrides):
    a checkpoint of seeded weights saved by the ``Trainer`` of its XE preset
    (``train_xe(epochs=0)`` is a no-op), loaded and decoded at beam 5. No
    entry point of its own, no option that picks an implementation."""
    from cst_captioning_tpu.cli import eval as cli_eval
    from cst_captioning_tpu.train.trainer import Trainer

    over = _tiny_overrides()
    ds, paths = _dataset(tmp_path, 6)
    cfg = get_preset("mimo_v2_5_ep16_xe").override(
        **over, data__batch_size=2, train__ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(cfg, ds, None, use_mesh=False)
    assert trainer.train_xe(epochs=0) is None
    trainer.ckpt.save(jax.device_get(trainer.state), None)
    trainer.close()
    ds.close()
    args = ["--preset", "mimo_v2_5_ep16_eval_beam5",
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


@pytest.mark.parametrize("setup", ["rolls"], indirect=True)
def test_a_gradient_passes_through_the_teacher_forcing(setup):
    """The XE preset's loss is differentiable through ``__call__`` (the
    prefix attention in its compiled-loop form, the experts' walk in its
    static spelling): every parameter a caption position reads gets a
    gradient."""
    _cfg, model, params, feats, masks, labels = setup

    def loss(p):
        logp = jax.nn.log_softmax(model.apply(p, feats, masks, labels), axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], axis=-1).mean()

    grads = jax.jit(jax.grad(loss))(params)["params"]["decoder"]
    assert np.isfinite(np.asarray(grads["layers_1"]["attention_sink_bias"])).all()
    assert np.abs(np.asarray(grads["layers_1"]["attention_sink_bias"])).max() > 0
    assert np.abs(np.asarray(grads["layers_2"]["experts_up_proj"])).max() > 0
    assert np.abs(np.asarray(grads["layers_0"]["k_proj"])).max() > 0

