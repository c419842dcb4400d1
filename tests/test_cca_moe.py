"""The sixth decoder kind (``ModelConfig.decoder = "cca_moe"``,
models/cca_moe.py; the attention in ops/window_attention.py; the router and
the experts' walk in models/experts.py) against its plain reference
(benchmark/reference_cca_moe.py), at a small size on seeded random weights,
float32 stated: teacher forcing (one forward over prefix and caption) and
prefill then 30 single steps through the cache and the convolution tails,
behind a whole prefix, short ones, ones that end on a tile's edge and one of a
single slot; the reference's two spellings held to each other; seven controls
that must fail the comparison (the value's shift, the q-k mean, the key
temperature, the router's carried stream, the no-expert output, the merge
vectors, the convolutions' reach back); the flash kernel under its third name
(interpret mode) against the walk over query blocks at 4 query heads a
key/value head of 128; the step for all lanes at once against the vmapped one,
the beam's gather permuting keys and tails alike, the search against the
prefix copied a lane and against the reference's own search, and a golden
file; the head tied to the embedding; two shares of eight experts adding up to
the uncut layer. Then the seams: the ``Evaluator``'s gauges and counters,
``cli/eval.py`` on the configuration's eval preset, ``obs/flops.py``,
``cli.obs_report``'s table.
"""

import dataclasses
import functools
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
from cst_captioning_tpu.decoding.beam import _gather_lanes
from cst_captioning_tpu.models import CaptionModel, captioner, cca_moe, experts
from cst_captioning_tpu.models.captioner import EncoderOutput
from cst_captioning_tpu.obs import flops
from cst_captioning_tpu.ops import window_attention as wa

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

T = 30
TINY = dict(
    decoder="cca_moe", vocab_size=32, modalities=(("patch", 16),), max_len=T,
    max_frames=48, dtype="float32", param_dtype="float32", hidden_size=32,
    num_hidden_layers=3, moe_intermediate_size=16, n_routed_experts=8,
    n_shared_experts=0, num_experts_per_tok=1, num_attention_heads=8,
    num_key_value_heads=2, head_dim=8, cca_time0=2, cca_time1=2,
    partial_rotary_factor=0.5, rope_theta=5e6, router_hidden_size=8,
    tie_word_embeddings=True, rms_norm_eps=1e-5, initializer_range=0.3,
    experts_held=8, expert_share_index=0, published_layers=40,
    first_layer_index=0)
# a whole prefix (48), short ones (35, 7), ones that end where a tile of 16
# does (32, 16) and a single slot, whose caption starts behind position 0
SLOTS = [48, 35, 32, 16, 7, 1]
# what float32 against float32 leaves is summation order: logits read up to
# 5, the largest difference seen is 4e-6
PARITY = 1e-4


@pytest.fixture(scope="module")
def ref():
    """The reference, loaded from its file as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        "reference_cca_moe",
        os.path.join(ROOT, "benchmark", "reference_cca_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _as_file(cfg: ModelConfig) -> dict:
    return json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))


def _batch(n, frames: int, seed=1, holes=False, steps=T):
    n = np.asarray(n)
    rng = np.random.default_rng(seed)
    mask = (np.arange(frames)[None] < n[:, None]).astype(np.float32)
    if holes:
        mask = np.stack([rng.permutation(row) for row in mask])
    feats = {"patch": rng.normal(size=(len(n), frames, 16)).astype(np.float32)}
    labels = rng.integers(4, TINY["vocab_size"], size=(len(n), steps)).astype(np.int32)
    return feats, {"patch": mask}, labels


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(**TINY)
    model = CaptionModel(cfg)
    feats, masks, labels = _batch(SLOTS, cfg.max_frames)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def _inputs(labels):
    return np.concatenate(
        [np.ones((len(labels), 1), np.int32), labels[:, :-1]], axis=1)


def _reference_logits(ref, params, model: dict, feats, masks, labels,
                      entry="forward"):
    with jax.default_matmul_precision("highest"):
        return np.asarray(jax.jit(lambda p: getattr(ref, entry)(
            p, model, feats, masks, jnp.asarray(_inputs(labels)),
            lambda x: x))(params))


def _picked(logits, labels):
    logp = jax.nn.log_softmax(jnp.asarray(logits), axis=-1)
    return np.take_along_axis(np.asarray(logp), labels[..., None], -1)[..., 0]


def test_init_declares_every_parameter_without_a_forward_and_no_head(setup):
    """One leaf a parameter, stacked over the three layers; the head is the
    embedding, so no head leaf exists; what is an identity at one or zero is
    drawn around it."""
    _cfg, _model, params, *_ = setup
    dec = params["params"]["decoder"]
    assert set(dec) == {"embed_patch", "embed_tokens", "norm", "layers"}
    merges = {f"{s}_{a}_{b}" for s in ("attn", "moe") for a in ("res", "out")
              for b in ("scale", "bias")}
    assert set(dec["layers"]) == merges | {
        "input_layernorm", "q_proj", "k_proj", "v_proj", "v_shift_proj",
        "conv0_w", "conv0_b", "conv1_w", "conv1_b", "temp", "o_proj",
        "post_attention_layernorm", "router_down", "router_eda", "router_norm",
        "router_w1", "router_b1", "router_w2", "router_b2", "router_w3",
        "router_b3", "router_bias", "experts_gate_proj", "experts_up_proj",
        "experts_down_proj"}
    shapes = {k: v.shape for k, v in dec["layers"].items()}
    assert shapes["q_proj"] == (3, 32, 64) and shapes["k_proj"] == (3, 32, 16)
    assert shapes["v_proj"] == shapes["v_shift_proj"] == (3, 32, 8)
    assert shapes["conv0_w"] == (3, 2, 80) and shapes["conv1_w"] == (3, 2, 10, 8, 8)
    assert shapes["temp"] == (3, 2) and shapes["router_eda"] == (3,)
    assert shapes["router_w3"] == (3, 8, 9) and shapes["router_bias"] == (3, 9)
    assert shapes["experts_up_proj"] == (3, 8, 32, 16)
    for name in merges | {"temp", "router_eda", "conv0_b", "conv1_b",
                          "router_b1", "router_b2", "router_b3", "router_bias"}:
        leaf = np.asarray(dec["layers"][name])
        assert leaf.std() > 0 and not np.isin(leaf, (0.0, 1.0)).any(), name
    assert abs(np.asarray(dec["layers"]["router_eda"]).mean() - 0.5) < 0.5


def test_teacher_forced_logits_match_the_reference(setup, ref):
    """``__call__`` (one forward over each clip's prefix and its caption
    behind it, the convolutions and the value's shift along the one sequence)
    against the reference's, logits compared: float32 on both sides."""
    cfg, model, params, feats, masks, labels = setup
    logits = jax.jit(model.apply)(params, feats, masks, labels)
    assert logits.shape == (len(labels), T, cfg.vocab_size)
    assert logits.dtype == jnp.float32
    want = _reference_logits(ref, params, _as_file(cfg), feats, masks, labels)
    assert np.abs(want).max() > 3.0         # a peaked distribution, not noise
    np.testing.assert_allclose(np.asarray(logits), want, atol=PARITY)
    picked = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(logits, labels), picked, atol=PARITY / 2)


def test_the_references_two_spellings_agree(setup, ref):
    """The prefix's block handing the caption its keys, values and the three
    things its first position reaches back for, against ONE sequence a clip
    with nothing handed over."""
    cfg, _model, params, feats, masks, labels = setup
    model = _as_file(cfg)
    np.testing.assert_allclose(
        _reference_logits(ref, params, model, feats, masks, labels),
        _reference_logits(ref, params, model, feats, masks, labels,
                          entry="forward_whole"), atol=PARITY / 4)


def test_prefill_then_thirty_tokens_through_cache_and_tails_match_the_full_forward(
        setup, ref):
    """30 single steps on the carry (the clip's prefix keys in the latent,
    the lane's own keys that grow, and the convolution tail: at the first
    step the prefix's last position, then the lane's own last) against the
    reference's full forward, which has no cache; and what each call counted."""
    cfg, model, params, feats, masks, labels = setup
    B, F, L = len(labels), cfg.max_frames, cfg.num_hidden_layers
    n = masks["patch"].sum(1).astype(int)
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    keys, values = enc.memory
    # once a clip, head-major, every layer: 2 key/value heads of 8 where the
    # stream is 32 wide
    assert keys.shape == values.shape == (B, L, 2, F, 8)
    assert enc.memory_proj.shape == (B, 0)
    c = enc.carry
    assert c.k.shape == c.v.shape == (B, L, 2, T, 8)
    assert c.tail_c.shape == c.tail_a.shape == (B, L, 80)
    assert c.tail_v.shape == (B, L, 8)
    assert all(x.shape[0] == B for x in jax.tree.leaves(c))
    assert np.abs(np.asarray(c.tail_c)).min(axis=-1).max() > 0  # the clip's
    assert c.routed.shape == (B, L, 9)
    # the prefix's pairs, one layer: i + 1 summed over a clip's slots; the
    # last layer's FFN over the prefix is not run
    assert np.asarray(c.counted)[:, 0, 0].tolist() == (n * (n + 1) // 2).tolist()
    routed = np.asarray(c.routed)
    assert (routed[:, :2, -1] == n[:, None]).all() and (routed[:, 2] == 0).all()
    skipped0 = np.asarray(c.counted)[:, 0, 1]
    assert (routed[:, :2, :-1].sum((1, 2)) + skipped0 == 2 * n).all()
    assert skipped0.sum() > 0      # some rows chose no expert
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
    np.testing.assert_allclose(np.stack(got, 1), want, atol=PARITY)
    counted = np.stack(counted, 1)
    assert (counted[..., 0] == n[:, None] + np.arange(T)[None] + 1).all()
    # a row's one assignment a layer falls on an expert or on none
    routed = np.asarray(carry.routed)
    assert (routed[..., -1] == 1).all()
    assert (routed[..., :-1].sum((1, 2)) + counted[:, -1, 1] == L).all()


def test_missing_slots_are_as_if_they_were_not_there(setup, ref):
    cfg, model, params, _f, _m, labels = setup
    feats, masks, _ = _batch(SLOTS, cfg.max_frames, seed=5, holes=True)
    order = np.argsort(masks["patch"] == 0, axis=1, kind="stable")
    packed = {"patch": np.take_along_axis(feats["patch"], order[..., None], 1)}
    packed_mask = {"patch": np.take_along_axis(masks["patch"], order, 1)}
    apply = jax.jit(model.apply)
    got = np.asarray(apply(params, feats, masks, labels))
    np.testing.assert_array_equal(
        got, np.asarray(apply(params, packed, packed_mask, labels)))
    want = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    np.testing.assert_allclose(_picked(got, labels), want, atol=PARITY / 2)


# ---- the controls: each must FAIL the comparison the tests above pass ----------


def _layers(params, **leaves):
    """``params`` with the stacked leaves ``leaves`` replaced (a callable is
    given the leaf)."""
    dec = dict(params["params"]["decoder"])
    dec["layers"] = {**dec["layers"], **{
        k: v(dec["layers"][k]) if callable(v) else v for k, v in leaves.items()}}
    return {"params": {"decoder": dec}}


def _no_merge_vectors(params):
    return _layers(params, **{
        f"{s}_{a}_{b}": (jnp.ones_like if b == "scale" else jnp.zeros_like)
        for s in ("attn", "moe") for a in ("res", "out") for b in ("scale", "bias")})


def _never_no_expert(params):
    return _layers(params, router_b3=lambda b: b.at[:, -1].set(-1e9))


def _no_reach_back(params):
    return _layers(params, conv0_w=lambda w: w.at[:, 0].set(0.0),
                   conv1_w=lambda w: w.at[:, 0].set(0.0))


def _value_unshifted(ref):
    def value(p, u, first, r):
        late = r(u) @ r(p["v_shift_proj"])
        return jnp.concatenate([r(u) @ r(p["v_proj"]), late], -1), late
    return {"value": value}


# name -> (the parameters altered, the reference's functions replaced)
CONTROLS = {
    "the_values_shift_left_out": (lambda p: p, _value_unshifted),
    "the_qk_mean_left_out": (
        lambda p: p, lambda ref: {"qk_mean": lambda mq, mk, q0, k0: (mq, mk)}),
    "the_key_temperature_left_out": (
        lambda p: _layers(p, temp=jnp.ones_like), lambda ref: {}),
    "the_routers_carried_stream_left_out": (
        lambda p: _layers(p, router_eda=jnp.zeros_like), lambda ref: {}),
    "the_no_expert_output_left_out": (_never_no_expert, lambda ref: {}),
    "the_merge_vectors_left_out": (_no_merge_vectors, lambda ref: {}),
    "the_convolutions_reach_back_left_out": (_no_reach_back, lambda ref: {}),
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_departure_from_the_equations_fails_the_comparison(
        setup, ref, control, monkeypatch):
    """The reference with one of the layer's rules left out, in the sound
    reference's place: the program's logits, which sit within ``PARITY`` of
    the sound reference's, leave the altered one's by hundreds of times
    that."""
    cfg, model, params, feats, masks, labels = setup
    got = np.asarray(jax.jit(model.apply)(params, feats, masks, labels))
    alter, replace = CONTROLS[control]
    for name, fn in replace(ref).items():
        monkeypatch.setattr(ref, name, fn)
    other = _reference_logits(ref, alter(params), _as_file(cfg), feats, masks,
                              labels)
    assert np.abs(got - other).max() > 200 * PARITY
    assert np.abs(_picked(got, labels) - _picked(other, labels)).mean() \
        > 20 * PARITY


# ---- the kernel under its third name ---------------------------------------------


@pytest.mark.parametrize("tiles", [(128, 128), (64, 128), (128, 64)])
def test_flash_kernel_equals_the_walk_over_query_blocks_at_the_latent_heads(tiles):
    """``cca_prefill(impl="pallas")`` in interpret mode against the ``xla``
    oracle at the published head layout: 8 query heads over 2 key/value heads
    (4 a block), keys and values of 128; a whole clip and one that ends
    inside a tile. A row's outputs from its ``n`` on are not defined."""
    keys = jax.random.split(jax.random.key(0), 3)
    P, n = 256, jnp.array([256, 130])
    q = jax.random.normal(keys[0], (2, P, 8, 128))
    k = jax.random.normal(keys[1], (2, P, 2, 128))
    v = jax.random.normal(keys[2], (2, P, 2, 128))
    want = np.asarray(wa.cca_prefill(q, k, v, n, impl="xla"))
    got = np.asarray(wa.cca_prefill(q, k, v, n, impl="pallas", tiles=tiles))
    np.testing.assert_allclose(got[0], want[0], atol=2e-5)
    np.testing.assert_allclose(got[1, :130], want[1, :130], atol=2e-5)
    # and it is the full layers' kernel body: the same numbers under that name
    np.testing.assert_array_equal(
        got, np.asarray(wa.full_prefill(q, k, v, n, impl="pallas", tiles=tiles)))


def test_the_prefix_through_the_kernel_is_the_prefix_through_the_walk(
        setup, monkeypatch):
    """The whole encoder pass with the kernel (interpret mode, tiles of 16:
    the clips of 32 and 16 slots end on a tile's edge) leaves the caption the
    keys, values and tails the compiled-loop form leaves it."""
    _cfg, model, params, feats, masks, _labels = setup
    encode = lambda: jax.jit(lambda p: model.apply(  # noqa: E731
        p, feats, masks, method=CaptionModel.encode))(params)
    want = encode()
    monkeypatch.setattr(cca_moe, "mixer_impl", lambda: "pallas")
    monkeypatch.setattr(wa, "cca_prefill",
                        functools.partial(wa.cca_prefill, tiles=(16, 16)))
    got = encode()
    n = masks["patch"].sum(1).astype(int)
    live = (np.arange(48)[None] < n[:, None])[:, None, None, :, None]
    for a, b in zip(got.memory, want.memory):
        np.testing.assert_allclose(np.asarray(a) * live, np.asarray(b) * live,
                                   atol=PARITY)
    for a, b in zip(jax.tree.leaves(got.carry), jax.tree.leaves(want.carry)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=PARITY)


# ---- the beam ---------------------------------------------------------------------


def _search(model, params, feats, masks, beam, impl="lanes"):
    return jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=beam, beam_impl=impl,
        return_tally=True))(params)


@pytest.mark.parametrize("beam", [1, 3, 5])
def test_the_step_for_all_lanes_is_the_vmapped_step(setup, beam, monkeypatch):
    """``lane_decode_step`` calls this kind's step with all lanes at once;
    vmapped a lane like the other kinds' (the held experts' loops masked to
    the longest) it emits the same tokens and counts, and scores that agree
    to float32's last bits (the lanes' rows meet the weights in one product
    or in one a lane)."""
    _cfg, model, params, feats, masks, _labels = setup
    lanes = _search(model, params, feats, masks, beam)
    monkeypatch.setattr(captioner, "ALL_LANES", ())
    vmapped = _search(model, params, feats, masks, beam)
    np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(vmapped[0]))
    np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(vmapped[1]),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(lanes[2]), jax.tree.leaves(vmapped[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_all_lanes_at_once_is_one_lane_at_a_time(setup):
    _cfg, model, params, feats, masks, labels = setup
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    W = 3
    carry = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (W,) + a.shape),
                         enc.carry)
    tokens = jnp.asarray(labels[:, :W].T)                  # [W, B], distinct
    lanes = jax.jit(lambda p, c, t: model.apply(
        p, c, t, bank, method=CaptionModel.decode_lanes))
    one = jax.jit(lambda p, c, t: model.apply(
        p, c, t, bank, method=CaptionModel.decode_step))
    for _ in range(2):
        carry_l, logits = lanes(params, carry, tokens)
        for w in range(W):
            c_w, l_w = one(params, jax.tree.map(lambda a: a[w], carry), tokens[w])
            np.testing.assert_allclose(np.asarray(logits[w]), np.asarray(l_w),
                                       atol=PARITY / 4)
            for a, b in zip(jax.tree.leaves(carry_l), jax.tree.leaves(c_w)):
                np.testing.assert_allclose(np.asarray(a[w]), np.asarray(b),
                                           atol=PARITY / 4)
        carry = carry_l


def test_a_permutation_of_parents_permutes_keys_and_tails_alike(setup):
    """The beam reorders its hypotheses by gathering every carry leaf by
    parent: a lane that takes over parent ``p``'s hypothesis has to take its
    caption keys AND its convolution tail, or its next step mixes another
    hypothesis' last position into its queries and keys."""
    _cfg, model, params, feats, masks, labels = setup
    B, W = len(labels), 4
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    lanes = jax.jit(lambda p, c, t: model.apply(
        p, c, t, bank, method=CaptionModel.decode_lanes))
    carry = jax.tree.map(lambda a: jnp.broadcast_to(a[None], (W,) + a.shape),
                         enc.carry)
    carry, _ = lanes(params, carry, jnp.asarray(labels[:, :W].T))
    tail = np.asarray(carry.tail_c)
    assert np.abs(tail[0] - tail[1]).max() > 1e-2       # the lanes differ
    rng = np.random.default_rng(0)
    parent = np.stack([rng.permutation(W) for _ in range(B)])       # [B, W]
    moved = _gather_lanes(carry, jnp.asarray(parent))
    rows = np.arange(B)
    for name in ("k", "v", "tail_c", "tail_a", "tail_v"):
        a, b = np.asarray(getattr(moved, name)), np.asarray(getattr(carry, name))
        for w in range(W):
            np.testing.assert_array_equal(a[w], b[parent[:, w], rows])
    # lane w of the moved state goes on as lane parent[., w] of the original
    tokens = labels[:, W:2 * W].T                                   # [W, B]
    _, after = lanes(params, moved, jnp.asarray(tokens))
    back = np.empty_like(tokens)
    for w in range(W):
        back[parent[:, w], rows] = tokens[w]
    _, before = lanes(params, carry, jnp.asarray(back))
    for w in range(W):
        np.testing.assert_allclose(
            np.asarray(after)[w], np.asarray(before)[parent[:, w], rows],
            atol=PARITY / 4)
    # with the tails left where they were, the logits leave by far more
    stale = dataclasses.replace(moved, tail_c=carry.tail_c, tail_a=carry.tail_a,
                                tail_v=carry.tail_v)
    _, wrong = lanes(params, stale, jnp.asarray(tokens))
    assert np.abs(np.asarray(wrong) - np.asarray(after)).max() > 100 * PARITY


@pytest.mark.parametrize("beam", [3, 5])
def test_beam_with_the_prefix_held_once_emits_what_a_copy_a_lane_does(
        setup, ref, beam):
    """"lanes" closes over the encoder output (one copy of a clip's prefix
    keys), "reference" tiles it a lane and runs the one-lane step over the
    flattened rows: the same tokens and counts, scores to float32's last
    bits. At beam 5 both emit the reference's own search's captions."""
    cfg, model, params, feats, masks, _labels = setup
    lanes = _search(model, params, feats, masks, beam)
    tiled = _search(model, params, feats, masks, beam, impl="reference")
    np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(tiled[0]))
    np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(tiled[1]),
                               rtol=2e-6)
    for a, b in zip(jax.tree.leaves(lanes[2]), jax.tree.leaves(tiled[2])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    routed, counted = lanes[2]
    assert routed.shape == (3, 9) and counted.shape == (1, 2)
    if beam == 5:
        tokens, score = jax.jit(lambda p: ref.beam_search(
            p, _as_file(cfg), feats, masks, 5, T))(params)
        np.testing.assert_array_equal(np.asarray(lanes[0]), np.asarray(tokens))
        np.testing.assert_allclose(np.asarray(lanes[1]), np.asarray(score),
                                   atol=10 * PARITY)


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


@pytest.mark.parametrize("impl", ["lanes", "reference"])
def test_beam_outputs_are_the_golden_s(setup, impl):
    """Tokens of beam 5 on seeded weights as the commit that brought this
    decoder kind emitted them, and its scores to a float32 summation order
    (tests/golden_beam_pr50.json, written by running these lines on it)."""
    _cfg, model, params, feats, masks, _labels = setup
    tokens, score = jax.jit(lambda p: beam_search(
        model, p, feats, masks, beam_size=5, beam_impl=impl)[:2])(params)
    with open(os.path.join(ROOT, "tests", "golden_beam_pr50.json")) as f:
        want = json.load(f)["cca_moe"]
    assert np.asarray(tokens).tolist() == want["tokens"]
    np.testing.assert_allclose(np.asarray(score), want["score"], rtol=1e-5)


# ---- the tie, the share ----------------------------------------------------------


def test_the_head_is_the_embedding(setup):
    """No head leaf (the first test); a token's logit is its embedding row
    against the final hidden state: doubling the row of a token no caption
    holds doubles that token's logit and moves no other."""
    _cfg, model, params, feats, masks, labels = setup
    unused = 3          # UNK: the labels are drawn from 4 on
    assert not (labels == unused).any()
    dec = dict(params["params"]["decoder"])
    dec["embed_tokens"] = dec["embed_tokens"].at[unused].multiply(2.0)
    apply = jax.jit(model.apply)
    a = np.asarray(apply(params, feats, masks, labels))
    b = np.asarray(apply({"params": {"decoder": dec}}, feats, masks, labels))
    np.testing.assert_allclose(b[..., unused], 2 * a[..., unused], rtol=1e-6)
    np.testing.assert_array_equal(np.delete(b, unused, -1), np.delete(a, unused, -1))


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(ref):
    """Two shares of eight experts each (what two chips would hold of a
    16-expert layer), each routing over all 17 outputs, computed by the
    program's router and walk and by the reference's given the same share: a
    share's program part is its reference part, the two parts sum to the
    uncut reference's layer, and a row that chose no expert adds nothing in
    either."""
    rng = np.random.default_rng(0)
    h, m, E, R, held, N = 32, 16, 16, 8, 8, 64
    draw = lambda *s: jnp.asarray(rng.normal(size=s) * 0.3, jnp.float32)  # noqa: E731
    z, r_prev = draw(N, h) / 0.3, draw(N, R)
    whole = {
        "router_down": draw(h, R), "router_eda": jnp.float32(0.5),
        "router_norm": 1 + draw(R), "router_w1": draw(R, R), "router_b1": draw(R),
        "router_w2": draw(R, R), "router_b2": draw(R),
        # the last layer drawn wide, so that the choice is the token's, and
        # the balancing bias leaning to the no-expert output, so that some
        # rows choose it (it moves the choice and no weight)
        "router_w3": 10 * draw(R, E + 1), "router_b3": draw(E + 1),
        "router_bias": (draw(E + 1) / 30).at[-1].add(0.15),
        "experts_gate_proj": draw(E, h, m), "experts_up_proj": draw(E, h, m),
        "experts_down_proj": draw(E, m, h)}
    stacked = lambda p: tuple(  # noqa: E731
        p[f"experts_{n}_proj"] for n in ("gate", "up", "down"))
    sizes = dict(n_routed_experts=E, rms_norm_eps=1e-5)
    same = lambda y: y  # noqa: E731
    with jax.default_matmul_precision("highest"):
        r_ref, w = ref.route(whole, sizes, z, r_prev, same)
        uncut = np.asarray(ref.experts(
            stacked(whole), dict(sizes, experts_held=E, expert_share_index=0),
            z, w, same))
        r_l, chosen, weight = experts.route_mlp(whole, z, r_prev, 1e-5)
        np.testing.assert_allclose(np.asarray(r_l), np.asarray(r_ref), atol=1e-5)
        none = np.asarray(chosen) == E
        assert 0 < none.sum() < N
        total = np.zeros_like(uncut)
        for share in range(E // held):
            cut = lambda a: a[share * held:(share + 1) * held]  # noqa: E731
            p = {**whole, **{name: cut(whole[name]) for name in whole
                             if name.startswith("experts_")}}
            part = np.asarray(ref.experts(
                stacked(p), dict(sizes, experts_held=held,
                                 expert_share_index=share), z, w, same))
            # the program's walk over the share's stacked leaves [1, held, ..]
            got, tally = experts.held_experts(
                z, chosen[:, None], weight[:, None], jnp.ones((N,), bool),
                *(a[None] for a in stacked(p)), share * held, E,
                differentiable=False, layer=0)
            np.testing.assert_allclose(np.asarray(got), part, atol=2e-5)
            assert (part[none] == 0).all() and (np.asarray(got)[none] == 0).all()
            mine = (np.asarray(chosen) // held) == share
            assert int(np.asarray(tally)[:, :-1].sum()) == int((mine & ~none).sum())
            assert int(np.asarray(tally)[:, -1].sum()) == N
            total += part
    assert np.abs(uncut).max() > 0.05
    np.testing.assert_allclose(total, uncut, atol=2e-5)


# ---- the seams -------------------------------------------------------------------


def test_the_lstm_only_entry_points_and_the_refused_sizes_say_so(setup):
    from cst_captioning_tpu.serving.engine import CaptionService

    cfg, model, params, feats, masks, labels = setup
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    with pytest.raises(NotImplementedError, match="cca_moe"):
        model.apply(params, enc, labels, method=CaptionModel.teacher_force_logps)
    with pytest.raises(ValueError, match="rl.enabled"):
        get_preset("zaya1_8b_20l_xe").override(rl__enabled=True)
    for bad in ({"cca_time0": 3}, {"tie_word_embeddings": False},
                {"num_experts_per_tok": 2}):
        with pytest.raises(ValueError, match="width 2"):
            CaptionModel(ModelConfig(**{**TINY, **bad})).init(
                jax.random.key(0), feats, masks, labels)
    with pytest.raises(ValueError, match="no share"):
        CaptionModel(ModelConfig(**{**TINY, "expert_share_index": 1})).init(
            jax.random.key(0), feats, masks, labels)
    with pytest.raises(NotImplementedError, match="cca_moe"):
        CaptionService(model, params, None)


def test_the_preset_holds_the_published_widths():
    mc = get_preset("zaya1_8b_20l_eval_beam5").model
    assert (mc.hidden_size, mc.moe_intermediate_size, mc.router_hidden_size) == \
        (2048, 2048, 256)
    assert (mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim) == \
        (8, 2, 128)
    assert (mc.cca_time0, mc.cca_time1, mc.partial_rotary_factor,
            mc.rope_theta, mc.rms_norm_eps) == (2, 2, 0.5, 5e6, 1e-5)
    assert (mc.n_routed_experts, mc.num_experts_per_tok, mc.experts_held,
            mc.expert_share_index) == (16, 1, 16, 0)
    assert (mc.vocab_size, mc.tie_word_embeddings, mc.num_hidden_layers,
            mc.published_layers) == (262272, True, 20, 40)
    assert cca_moe.rotary_dims(mc) == 64 and cca_moe.latent_channels(mc) == 1280
    ev = get_preset("zaya1_8b_20l_eval_beam5").eval
    assert (ev.max_len, ev.beam_size, ev.beam_impl, ev.prefill_program) == \
        (30, 5, "lanes", True)
    model = CaptionModel(mc)
    shapes = jax.eval_shape(lambda: model.init(
        jax.random.key(0), {"patch": jnp.zeros((1, 8, 1024))},
        {"patch": jnp.ones((1, 8))}, jnp.zeros((1, 30), jnp.int32)))
    count = sum(x.size for x in jax.tree.leaves(shapes))
    h, d = 2048, 128
    cca = h * 10 * d + h * 2 * (d // 2) * 2 + 8 * d * h \
        + 3 * 1280 + 2 * 10 * d * d + 1280 + 2
    router = h * 256 + 1 + 256 + 2 * (256 * 256 + 256) + 256 * 17 + 17 + 17
    layer = cca + router + 2 * h + 8 * h + 16 * 3 * h * 2048
    assert count == 20 * layer + 262272 * h + 1024 * h + h
    assert count == 4_690_897_636
    assert "lm_head" not in shapes["params"]["decoder"]


def test_flops_dispatch_on_the_decoder_kind():
    mc = get_preset("zaya1_8b_20l_xe").model
    short = flops.cca_moe_per_tok_flops(mc, context=100)
    late = flops.cca_moe_per_tok_flops(mc, context=14336)
    # the pairs grow with the context in every layer: 8 heads, keys and
    # values of 128
    assert late - short == 20 * 2 * 8 * 2 * 128 * (14336 - 100)
    attn = 2 * 2048 * 12 * 128 + 2 * 1024 * 2048 + 2 * 10 * 2 * 128 * 128
    router = 2 * 2048 * 256 + 4 * 256 * 256 + 2 * 256 * 17
    expert = 2 * 3 * 2048 * 2048 * 16 / 17
    assert short == pytest.approx(
        20 * (attn + router + expert + 2 * 8 * 2 * 128 * 100))
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


def test_evaluator_tells_the_kinds_of_state_apart_and_counts_pairs_experts_and_skips(
        tmp_path, setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.obs.report import build_report, render_report

    cfg, model, params, *_ = setup
    ds, _paths = _dataset(tmp_path, 12)
    base = dataclasses.replace(
        get_preset("zaya1_8b_20l_eval_beam5").eval, max_len=T,
        metrics=("CIDEr-D",), split="train")
    obs.REGISTRY.reset()
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
        obs.REGISTRY.reset()
        ds.close()
    assert split["captions"] == whole["captions"] and len(split["captions"]) == 12
    g, c, h = snap["gauges"], snap["counters"], snap["histograms"]
    # 4 clips, float32, 3 layers of 2 key/value heads of 8, keys and values:
    # a clip's 48 prefix positions once; a lane's 30 caption positions and
    # its tail (80 + 80 + 8 numbers a layer)
    kv = 3 * 2 * 2 * 8 * 4
    assert g["decode.prefix_key_bytes"] == kv * 4 * 48
    assert g["decode.conv_tail_bytes"] == 3 * 168 * 4 * 4 * 5
    assert g["decode.cache_bytes"] == g["decode.prefix_key_bytes"] \
        + g["decode.conv_tail_bytes"] + kv * 4 * 5 * T
    # copied a lane, the clip's part is five times as large
    assert snap_tiled["gauges"]["decode.prefix_key_bytes"] == kv * 4 * 5 * 48
    assert g["moe.experts_held"] == 8
    # every expert is held: an assignment is local or chose none
    assert 0 < c["moe.assignments.skipped"] < c["moe.assignments"]
    assert c["moe.assignments.local"] + c["moe.assignments.skipped"] \
        == c["moe.assignments"]
    assert c["attn.pairs_causal"] > 0 and "attn.pairs_window" not in c
    assert h["moe.expert_rows"]["count"] == 3 * (3 * 8)   # batches x layers x held
    events = [json.loads(line) for line in open(tmp_path / "obs" / "events.jsonl")]
    names = [e["name"] for e in events if e.get("event") == "span"]
    assert names.count("eval.prefill") == names.count("eval.decode") == 3
    text = render_report(build_report(events))
    assert "prefix keys and values in the latent" in text
    assert "convolution tails" in text and "chose no expert" in text
    assert "routed experts: 8 held" in text and "plain causal" not in text


def test_cli_eval_runs_the_eval_preset_end_to_end(tmp_path, capsys):
    """``cli/eval.py`` on the configuration's eval preset (tiny overrides):
    a checkpoint of seeded weights saved by the ``Trainer`` of its XE preset
    (``train_xe(epochs=0)`` is a no-op), loaded and decoded at beam 5. No
    entry point of its own, no option that picks an implementation."""
    from cst_captioning_tpu.cli import eval as cli_eval
    from cst_captioning_tpu.train.trainer import Trainer

    over = _tiny_overrides()
    ds, paths = _dataset(tmp_path, 6)
    cfg = get_preset("zaya1_8b_20l_xe").override(
        **over, data__batch_size=2, train__ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(cfg, ds, None, use_mesh=False)
    assert trainer.train_xe(epochs=0) is None
    trainer.ckpt.save(jax.device_get(trainer.state), None)
    trainer.close()
    ds.close()
    args = ["--preset", "zaya1_8b_20l_eval_beam5",
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


def test_a_gradient_passes_through_the_teacher_forcing(setup):
    """The XE preset's loss is differentiable through ``__call__`` (the scan
    over the layers, the prefix attention in its compiled-loop form, the
    experts' walk in its static spelling): every kind of parameter a caption
    position reads gets a gradient, the tied embedding from both its uses."""
    _cfg, model, params, feats, masks, labels = setup

    def loss(p):
        logp = jax.nn.log_softmax(model.apply(p, feats, masks, labels), axis=-1)
        return -jnp.take_along_axis(
            logp, jnp.asarray(labels)[..., None], axis=-1).mean()

    grads = jax.jit(jax.grad(loss))(params)["params"]["decoder"]
    for name in ("temp", "router_eda", "conv0_w", "conv1_w", "v_shift_proj",
                 "experts_up_proj", "router_w3", "attn_res_scale", "moe_out_bias"):
        g = np.asarray(grads["layers"][name])
        assert np.isfinite(g).all() and np.abs(g).max() > 0, name
    assert np.abs(np.asarray(grads["embed_tokens"])).max() > 0
