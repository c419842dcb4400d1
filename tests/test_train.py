"""Training-layer tests, incl. the 8-fake-device DP equivalence (SURVEY §4.4)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.config.config import ModelConfig, TrainConfig
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.train import (
    create_train_state,
    make_mesh,
    make_optimizer,
    make_parallel_xe_step,
    make_xe_step,
    replicate,
    shard_batch,
)

B, F, T, V = 16, 4, 6, 17  # B divisible by 8 fake devices


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 8),),
        d_embed=12,
        d_hidden=12,
        d_att=6,
        encoder="temporal_attention",
        dropout=0.0,  # determinism for the DP-equivalence check
        max_len=T,
        max_frames=F,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 8)), jnp.float32)}
    masks = {"resnet": jnp.ones((B, F), jnp.float32)}
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    mask = jnp.ones((B, T), jnp.float32)
    # ragged masks: rows end at different lengths (exercises normalization)
    mask_np = np.ones((B, T), np.float32)
    for i in range(B):
        mask_np[i, 2 + (i % 4):] = 0.0
    mask = jnp.asarray(mask_np)
    weights = jnp.asarray(rng.uniform(0.5, 1.5, size=(B,)), jnp.float32)
    tx = make_optimizer(TrainConfig(lr=1e-3, grad_clip=1.0), steps_per_epoch=10)
    state = create_train_state(model, tx, (feats, masks, labels), seed=0)
    return model, state, (feats, masks, labels, mask, weights)


def test_single_device_step_decreases_loss(setup):
    model, state, batch = setup
    step = make_xe_step(model)
    losses = []
    for _ in range(8):
        state, m = step(state, *batch)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]
    assert int(state.step) == 8
    assert np.isfinite(losses).all()


def test_parallel_step_matches_single_device(setup):
    """psum-DP grads over 8 devices == single-device grads on the full batch."""
    model, state0, batch = setup
    assert len(jax.devices()) == 8, "conftest must provide 8 fake CPU devices"
    mesh = make_mesh()

    single = make_xe_step(model)
    parallel = make_parallel_xe_step(model, mesh)

    s_state, s_metrics = single(state0, *batch)

    p_state = replicate(mesh, state0)
    p_batch = shard_batch(mesh, batch)
    p_state, p_metrics = parallel(p_state, *p_batch)

    np.testing.assert_allclose(
        float(s_metrics["loss"]), float(p_metrics["loss"]), rtol=1e-5
    )
    # the gradient itself, not only the Adam step it produces: Adam is scale
    # invariant, so a gradient reduced twice (8x too large) moved the params
    # exactly like the right one and slipped past the check below
    np.testing.assert_allclose(
        float(s_metrics["grad_norm"]), float(p_metrics["grad_norm"]),
        rtol=1e-4,
    )
    # updated params identical (up to float assoc in psum ordering)
    flat_s = jax.tree_util.tree_leaves(s_state.params)
    flat_p = jax.tree_util.tree_leaves(p_state.params)
    # psum reassociation perturbs grads at float32 eps; Adam's rsqrt amplifies
    # that on near-zero second moments, so compare at 1e-3 not exact-bit level
    for a, b in zip(flat_s, flat_p):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5)


def test_parallel_step_runs_multiple_steps(setup):
    model, state0, batch = setup
    mesh = make_mesh()
    parallel = make_parallel_xe_step(model, mesh)
    state = replicate(mesh, state0)
    pb = shard_batch(mesh, batch)
    losses = []
    for _ in range(5):
        state, m = parallel(state, *pb)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0]


def test_lr_schedule_decay():
    from cst_captioning_tpu.train import make_lr_schedule

    cfg = TrainConfig(lr=1e-2, lr_decay=0.5, lr_decay_every=2)
    sched = make_lr_schedule(cfg, steps_per_epoch=10)
    assert float(sched(0)) == pytest.approx(1e-2)
    assert float(sched(19)) == pytest.approx(1e-2)
    assert float(sched(20)) == pytest.approx(5e-3)
    assert float(sched(40)) == pytest.approx(2.5e-3)
    const = make_lr_schedule(TrainConfig(lr=1e-3, lr_decay_every=0), 10)
    assert float(const(1000)) == pytest.approx(1e-3)


def test_make_optimizer_rejects_unknown():
    with pytest.raises(ValueError, match="unknown optimizer"):
        make_optimizer(TrainConfig(optimizer="adagrad"), 1)


def test_weighted_step_uses_weights(setup):
    """Zeroing a row's weight must change the computed loss."""
    model, state, (feats, masks, labels, mask, weights) = setup
    step = make_xe_step(model)
    _, m1 = step(state, feats, masks, labels, mask, weights)
    w2 = weights.at[0].set(0.0)
    _, m2 = step(state, feats, masks, labels, mask, w2)
    assert float(m1["loss"]) != float(m2["loss"])
