"""Sequence-parallelism parity: frame-sharded model == single-device model.

SURVEY.md §5 long-context row: shard the frame axis over the mesh, psum the
attention numerator/denominator pair. These tests pin the collective softmax,
the pooled carry init, decode, beam, and training gradients against the
unsharded implementation on 8 fake CPU devices.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map

from cst_captioning_tpu.config.config import ModelConfig, TrainConfig
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.parallel import (
    make_sp_decode,
    make_sp_forward,
    make_sp_rl_update,
    make_sp_xe_step,
    sp_batch_specs,
    sp_model,
)
from cst_captioning_tpu.train import create_train_state, make_optimizer
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

V, B, F, T = 20, 4, 16, 6   # F=16 shards 8 ways (2 frames/device)


def mesh_1d(axis="seq"):
    return Mesh(np.asarray(jax.devices()), (axis,))


def mesh_2d(data=2, seq=4):
    return Mesh(np.asarray(jax.devices()).reshape(data, seq), ("data", "seq"))


@pytest.fixture(scope="module", params=["temporal_attention", "meanpool"])
def setup(request):
    cfg = ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 10), ("c3d", 6)),
        d_embed=12,
        d_hidden=12,
        d_att=8,
        encoder=request.param,
        dropout=0.0,
        max_len=T,
        max_frames=F,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats = {
        "resnet": jnp.asarray(rng.normal(size=(B, F, 10)), jnp.float32),
        "c3d": jnp.asarray(rng.normal(size=(B, F, 6)), jnp.float32),
    }
    # ragged frame validity to exercise the masked collective softmax,
    # including one device's shard being fully masked for some rows
    masks = {
        k: jnp.asarray(
            (np.arange(F)[None, :] < rng.integers(3, F + 1, size=(B, 1))),
            jnp.float32,
        )
        for k in feats
    }
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def _place(mesh, cfg, feats, masks, data_axis=""):
    f_spec, m_spec = sp_batch_specs(cfg, data_axis)
    f = {k: jax.device_put(v, NamedSharding(mesh, f_spec[k])) for k, v in feats.items()}
    m = {k: jax.device_put(v, NamedSharding(mesh, m_spec[k])) for k, v in masks.items()}
    return f, m


def test_sp_forward_matches_single_device(setup):
    cfg, model, params, feats, masks, labels = setup
    want = model.apply(params, feats, masks, labels)

    mesh = mesh_1d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks)
    got = make_sp_forward(spm, mesh)(params, f, m, labels)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-5, atol=1e-5)


def test_sp_greedy_decode_matches_single_device(setup):
    from cst_captioning_tpu.decoding import greedy_decode

    cfg, model, params, feats, masks, _ = setup
    want, _ = greedy_decode(model, params, feats, masks, max_len=T)

    mesh = mesh_1d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks)
    got, samples = make_sp_decode(spm, mesh, num_rollouts=2, max_len=T)(
        params, f, m, jax.random.key(1)
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert samples.shape == (2, B, T)
    s = np.asarray(samples)
    assert (s >= 0).all() and (s < V).all()


def test_sp_beam_search_matches_single_device(setup):
    from cst_captioning_tpu.decoding import beam_search

    cfg, model, params, feats, masks, _ = setup
    want, _ = beam_search(model, params, feats, masks, beam_size=3, max_len=T)

    mesh = mesh_1d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks)
    sharded = jax.jit(shard_map(
        lambda p, fe, ma: beam_search(spm, p, fe, ma, beam_size=3, max_len=T)[0],
        mesh=mesh,
        in_specs=(P(),) + sp_batch_specs(cfg),
        out_specs=P(),
    ))
    got = sharded(params, f, m)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("data_axis", ["", "data"])
def test_sp_xe_step_matches_single_device(setup, data_axis):
    """SP (and DP x SP) gradients through the collective softmax are exact."""
    from cst_captioning_tpu.train.steps import make_xe_step

    cfg, model, params, feats, masks, labels = setup
    mask = jnp.ones((B, T), jnp.float32)
    weights = jnp.ones((B,), jnp.float32)
    tx = make_optimizer(TrainConfig(lr=1e-2, grad_clip=5.0), 10)
    state = create_train_state(model, tx, (feats, masks, labels), seed=3)

    s_state, s_m = make_xe_step(model)(state, feats, masks, labels, mask, weights)

    mesh = mesh_2d() if data_axis else mesh_1d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks, data_axis)
    step = make_sp_xe_step(spm, mesh, data_axis=data_axis)
    b_shard = (
        NamedSharding(mesh, P("data")) if data_axis
        else NamedSharding(mesh, P())
    )
    p_state, p_m = step(
        state,
        f,
        m,
        jax.device_put(labels, b_shard),
        jax.device_put(mask, b_shard),
        jax.device_put(weights, b_shard),
    )
    np.testing.assert_allclose(float(s_m["loss"]), float(p_m["loss"]), rtol=1e-5)
    for a, b in zip(
        jax.tree_util.tree_leaves(s_state.params),
        jax.tree_util.tree_leaves(p_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
        )


def test_sp_dp_greedy_decode_matches_single_device(setup):
    """make_sp_decode with a data axis (the product DP x SP layout): greedy
    tokens on a 2x4 mesh == the single-device decode."""
    from cst_captioning_tpu.decoding import greedy_decode

    cfg, model, params, feats, masks, _ = setup
    want, _ = greedy_decode(model, params, feats, masks, max_len=T)

    mesh = mesh_2d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks, "data")
    got, samples = make_sp_decode(
        spm, mesh, num_rollouts=2, max_len=T, data_axis="data"
    )(params, f, m, jax.random.key(1))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert samples.shape == (2, B, T)
    s = np.asarray(samples)
    assert (s >= 0).all() and (s < V).all()


def test_sp_rl_update_matches_single_device(setup):
    """make_sp_rl_update on a 2x4 mesh: same rollouts + advantages produce
    the same post-update params as the single-device REINFORCE update
    (gradients through the 'seq' attention collectives are exact)."""
    from jax.sharding import NamedSharding
    from cst_captioning_tpu.rl.scst import make_rl_update

    cfg, model, params, feats, masks, labels = setup
    K = 3
    rng = np.random.default_rng(5)
    samples = jnp.asarray(rng.integers(2, V, size=(K, B, T)), jnp.int32)
    advantage = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.asarray([1, 1, 1, 0], jnp.float32)  # one wrap-padded row

    tx = make_optimizer(TrainConfig(lr=1e-2, grad_clip=5.0), 10)
    state = create_train_state(model, tx, (feats, masks, labels), seed=3)
    s_state, s_m = make_rl_update(model)(
        state, feats, masks, samples, advantage, valid
    )

    mesh = mesh_2d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, feats, masks, "data")
    bshard = NamedSharding(mesh, P("data"))
    kb_shard = NamedSharding(mesh, P(None, "data"))
    for chunks in (1, 3):  # fused + rollout-axis gradient accumulation
        p_state, p_m = make_sp_rl_update(spm, mesh, chunks=chunks)(
            state, f, m,
            jax.device_put(samples, kb_shard),
            jax.device_put(advantage, kb_shard),
            jax.device_put(valid, bshard),
        )
        np.testing.assert_allclose(
            float(s_m["rl_loss"]), float(p_m["rl_loss"]), rtol=1e-5
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(s_state.params),
            jax.tree_util.tree_leaves(p_state.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5
            )


def test_sp_rl_update_word_embedding_gradient(setup):
    """The word embedding's gradient through ``make_sp_rl_update``, which
    differentiates outside ``shard_map`` (teacher forcing's backward pass is
    traced apart from its forward pass, the table is the same on every
    shard and the rows summed into it are not): plain SGD at rate 1 makes
    the leaf's change the gradient itself, and it equals the one-device
    update's. Few tokens, so that a table row sums many input cotangents
    across positions, rollouts and 'data' shards; and captions of every
    length, so that the 'data' shards run their loops to different depths
    (a sum over 'data' inside the loop, which is where jax puts the
    cotangent of a parameter typed the same on every shard, then meets no
    partner: the update ended the process)."""
    import optax
    from jax.sharding import NamedSharding
    from cst_captioning_tpu.rl.scst import make_rl_update

    cfg, model, params, feats, masks, labels = setup
    K = 3
    rng = np.random.default_rng(7)
    lens = rng.integers(1, T + 1, size=(K, B, 1))
    samples = jnp.asarray(
        np.where(np.arange(T) < lens, rng.integers(4, 7, size=(K, B, T)), 0),
        jnp.int32)
    advantage = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    state = create_train_state(model, optax.sgd(1.0), (feats, masks, labels),
                               seed=3)
    table = lambda st: np.asarray(  # noqa: E731
        st.params["params"]["cell"]["word_embed"]["embedding"])
    s_state, _ = make_rl_update(model)(
        state, feats, masks, samples, advantage, valid
    )
    want = table(state) - table(s_state)
    assert np.abs(want[4:7]).min() > 0 and not want[7:].any()

    mesh = mesh_2d()
    f, m = _place(mesh, cfg, feats, masks, "data")
    kb_shard = NamedSharding(mesh, P(None, "data"))
    for chunks in (1, 3):
        p_state, _ = make_sp_rl_update(sp_model(cfg), mesh, chunks=chunks)(
            state, f, m,
            jax.device_put(samples, kb_shard),
            jax.device_put(advantage, kb_shard),
            jax.device_put(valid, NamedSharding(mesh, P("data"))),
        )
        np.testing.assert_allclose(table(state) - table(p_state), want,
                                   rtol=1e-4, atol=1e-6)


def test_sp_handles_very_long_frame_axis(setup):
    """The SP design point: a frame axis far beyond one batch's usual size
    still decodes (each device holds 1/8th of the frames)."""
    cfg, model, params, feats, masks, _ = setup
    if cfg.encoder != "temporal_attention":
        pytest.skip("long-frame point test only needs one encoder")
    LONG = 512
    rng = np.random.default_rng(7)
    lf = {
        "resnet": jnp.asarray(rng.normal(size=(2, LONG, 10)), jnp.float32),
        "c3d": jnp.asarray(rng.normal(size=(2, LONG, 6)), jnp.float32),
    }
    lm = {k: jnp.ones((2, LONG), jnp.float32) for k in lf}
    want, _ = __import__("cst_captioning_tpu.decoding", fromlist=["greedy_decode"]).greedy_decode(
        model, params, lf, lm, max_len=T
    )
    mesh = mesh_1d()
    spm = sp_model(cfg)
    f, m = _place(mesh, cfg, lf, lm)
    got, _ = make_sp_decode(spm, mesh, max_len=T)(params, f, m, jax.random.key(0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
