"""Gradient-communication parity suite (parallel/comms.py).

Pins the ISSUE-11 acceptance contract on the 8 fake CPU devices:

- bucketed f32 allreduce is BIT-identical to the per-leaf psum spelling
  (psum is elementwise — coalescing cannot change a single bit), and the
  default ``CommConfig()`` path through the real step/update factories is
  bit-identical to the pre-PR ``comm=None`` spelling;
- bf16-on-the-wire stays within the pinned tolerance per reduction, and
  the f32 master accumulation keeps the drift bounded over 50 synthetic
  optimizer steps (rounding must not compound in the state);
- the overlapped ("defer") chunked update is bit-identical to its eager
  per-chunk-reduce reference spelling;
- the bucket planner orders by param family, respects the size target,
  and the config layer rejects the nonsense combinations at build time.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from cst_captioning_tpu.config.config import (
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    RLConfig,
    TrainConfig,
)
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.parallel.comms import (
    CommConfig,
    ledger,
    per_leaf_f32_bytes,
    plan_buckets,
    reduce_tree,
)
from cst_captioning_tpu.rl import make_parallel_rl_update
from cst_captioning_tpu.train import (
    create_train_state,
    make_mesh,
    make_optimizer,
    make_parallel_xe_step,
    replicate,
    shard_batch,
)

V = 17


def _param_like_tree(rng):
    """A params-shaped pytree whose paths hit the PARAM_PARTITION_RULES
    families (flatten order is alphabetical, deliberately != family order)."""
    shape = {
        "params": {
            "cell": {
                "out_proj": {"kernel": (24, V), "bias": (V,)},
                "word_embed": {"embedding": (V, 24)},
            },
            "encoder": {"embed_resnet": {"kernel": (8, 24), "bias": (24,)}},
            "init_h0": {"kernel": (24, 24)},
        }
    }
    return jax.tree.map(
        lambda s: jnp.asarray(rng.normal(size=s), jnp.float32),
        shape,
        is_leaf=lambda x: isinstance(x, tuple),
    )


def _reduce_on_mesh(tree, comm):
    mesh = make_mesh()
    fn = shard_map(
        lambda t: reduce_tree(t, "data", comm),
        mesh=mesh, in_specs=(P(),), out_specs=P(),
    )
    return jax.jit(fn)(tree)


# ---- planner (host-side) ----------------------------------------------------


def test_plan_buckets_family_order_and_size_target():
    tree = {
        "params": {
            "cell": {"word_embed": {"embedding": jax.ShapeDtypeStruct((4000, 32), jnp.float32)}},
            "encoder": {"embed_resnet": {"kernel": jax.ShapeDtypeStruct((64, 32), jnp.float32)}},
            "stray": jax.ShapeDtypeStruct((7,), jnp.float32),
        }
    }
    plan = plan_buckets(tree, CommConfig(bucket_mb=0.25))
    leaves_paths = [
        "params/cell/word_embed/embedding",   # flatten index 0
        "params/encoder/embed_resnet/kernel", # flatten index 1
        "params/stray",                       # flatten index 2
    ]
    order = [i for b in plan.buckets for i in b.indices]
    # family order: encoder_embed (rank 0) first, word_embed next, the
    # rule-less stray leaf last
    assert [leaves_paths[i] for i in order] == [
        "params/encoder/embed_resnet/kernel",
        "params/cell/word_embed/embedding",
        "params/stray",
    ]
    target = int(0.25 * (1 << 20))
    for b in plan.buckets:
        # a bucket only exceeds the target when a single leaf does
        assert b.bytes_on_wire <= target or len(b.indices) == 1
    # the 512 KB embedding exceeds the 256 KB target -> its own bucket
    [emb_bucket] = [b for b in plan.buckets if 0 in b.indices]
    assert emb_bucket.indices == (0,)
    assert plan.bytes_on_wire == per_leaf_f32_bytes(tree)


def test_plan_buckets_coalesces_small_leaves():
    tree = {f"params/x{i:02d}": jax.ShapeDtypeStruct((10,), jnp.float32)
            for i in range(12)}
    plan = plan_buckets(tree, CommConfig(bucket_mb=4.0))
    assert len(plan.buckets) == 1
    assert plan.buckets[0].bytes_on_wire == 12 * 10 * 4


def test_plan_buckets_zero_mb_is_per_leaf():
    tree = {"a": jax.ShapeDtypeStruct((5,), jnp.float32),
            "b": jax.ShapeDtypeStruct((6,), jnp.float32)}
    plan = plan_buckets(tree, CommConfig(bucket_mb=0.0))
    assert len(plan.buckets) == 2


def test_ledger_bf16_halves_wire_bytes():
    rng = np.random.default_rng(0)
    tree = _param_like_tree(rng)
    base = ledger(tree, None)
    bf16 = ledger(tree, CommConfig(dtype="bf16"))
    assert base["bytes_on_wire_per_update"] == per_leaf_f32_bytes(tree)
    assert base["bytes_on_wire_per_update"] == \
        2 * bf16["bytes_on_wire_per_update"]
    assert bf16["messages_per_update"] < base["messages_per_update"]


# ---- reduction parity on the 8-device mesh ----------------------------------


def test_bucketed_f32_bitexact_vs_per_leaf():
    rng = np.random.default_rng(1)
    tree = _param_like_tree(rng)
    ref = _reduce_on_mesh(tree, None)
    for mb in (4.0, 0.001, 0.0):
        got = _reduce_on_mesh(tree, CommConfig(bucket_mb=mb))
        for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_bf16_wire_within_tolerance():
    rng = np.random.default_rng(2)
    tree = _param_like_tree(rng)
    ref = _reduce_on_mesh(tree, None)
    got = _reduce_on_mesh(tree, CommConfig(dtype="bf16"))
    for a, b in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        a, b = np.asarray(a), np.asarray(b)
        assert b.dtype == a.dtype  # cast back to the leaf dtype
        # bf16 keeps 8 mantissa bits: relative error per element ~2^-8
        np.testing.assert_allclose(b, a, rtol=1.2e-2, atol=1e-6)


def test_bf16_master_accumulation_drift_bounded():
    """50 synthetic SGD steps with bf16-on-the-wire gradients against the
    f32 reference: params (the f32 master copy) must drift only by the
    accumulated per-step rounding, not compound — the pinned bound is ~10x
    the random-walk estimate sqrt(50) * 2^-8 * lr."""
    rng = np.random.default_rng(3)
    params = _param_like_tree(rng)
    lr = 0.01
    comm_bf = CommConfig(dtype="bf16")

    def run(comm):
        p = params
        for step in range(50):
            g = jax.tree.map(
                lambda x: jnp.asarray(
                    np.random.default_rng(step).normal(size=x.shape),
                    jnp.float32,
                ),
                p,
            )
            g = _reduce_on_mesh(g, comm)
            p = jax.tree.map(lambda x, gg: x - lr * gg, p, g)
        return p

    p_ref, p_bf = run(None), run(comm_bf)
    for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_bf)):
        # grads are psum'd over 8 devices (|g| ~ 8): per-step wire rounding
        # is ~8 * 2^-8, scaled by lr; 50 steps of it stays ~1e-2, far from
        # the O(1) error a compounding (bf16 state) bug would show
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-2, rtol=0
        )


# ---- the real factories: default-path bit-identity + overlap parity ---------


@pytest.fixture(scope="module")
def model_setup():
    B, F, T = 8, 3, 5
    cfg = ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 6),),
        d_embed=12,
        d_hidden=12,
        d_att=6,
        encoder="meanpool",
        dropout=0.0,
        max_len=T,
        max_frames=F,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 6)), jnp.float32)}
    masks = {"resnet": jnp.ones((B, F), jnp.float32)}
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    tx = make_optimizer(TrainConfig(lr=5e-2, grad_clip=5.0), 10)
    state = create_train_state(model, tx, (feats, masks, labels), seed=1)
    return model, state, feats, masks, labels


def _rl_args(mesh, state, feats, masks, K=4, B=8, T=5, seed=5):
    rng = np.random.default_rng(seed)
    samples = jnp.asarray(rng.integers(2, V, size=(K, B, T)), jnp.int32)
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    kb = jax.sharding.NamedSharding(mesh, P(None, "data"))
    return (
        replicate(mesh, state),
        *shard_batch(mesh, (feats, masks)),
        jax.device_put(samples, kb),
        jax.device_put(adv, kb),
        shard_batch(mesh, valid),
    )


def _assert_trees_bitequal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def test_default_comm_bit_identical_rl_update(model_setup):
    """Acceptance pin: the default config path (f32, no overlap) through
    the parallel RL update is BIT-identical to the pre-PR per-leaf psum
    spelling (comm=None IS that spelling, kept callable)."""
    model, state, feats, masks, _ = model_setup
    mesh = make_mesh()
    args = _rl_args(mesh, state, feats, masks)
    s0, m0 = make_parallel_rl_update(model, mesh, comm=None)(*args)
    s1, m1 = make_parallel_rl_update(model, mesh, comm=CommConfig())(*args)
    assert float(m0["rl_loss"]) == float(m1["rl_loss"])
    _assert_trees_bitequal(s0.params, s1.params)
    _assert_trees_bitequal(s0.opt_state, s1.opt_state)


def test_default_comm_bit_identical_xe_step(model_setup):
    model, state, feats, masks, labels = model_setup
    B, T = labels.shape
    mesh = make_mesh()
    batch = (feats, masks, labels, jnp.ones((B, T), jnp.float32),
             jnp.ones((B,), jnp.float32))
    args = (replicate(mesh, state), *shard_batch(mesh, batch))
    s0, m0 = make_parallel_xe_step(model, mesh, comm=None)(*args)
    s1, m1 = make_parallel_xe_step(model, mesh, comm=CommConfig())(*args)
    assert float(m0["loss"]) == float(m1["loss"])
    _assert_trees_bitequal(s0.params, s1.params)


def test_bf16_rl_update_within_tolerance(model_setup):
    model, state, feats, masks, _ = model_setup
    mesh = make_mesh()
    args = _rl_args(mesh, state, feats, masks)
    s0, m0 = make_parallel_rl_update(model, mesh, comm=None)(*args)
    s1, m1 = make_parallel_rl_update(
        model, mesh, comm=CommConfig(dtype="bf16")
    )(*args)
    np.testing.assert_allclose(
        float(m0["rl_loss"]), float(m1["rl_loss"]), rtol=1e-6
    )  # the loss never rides the wire — only grads are compressed
    # one Adam step from identical, zero-moment state moves every element
    # by ~lr * sign(g) (lr 5e-2). Each shard's LOCAL grad rides the wire in
    # bf16 and the shards' terms partly cancel, so an element whose summed
    # grad is near zero can land anywhere within +-lr of the f32 answer;
    # everything else moves by ~2^-8 of its update. A broken accumulation
    # (a dropped bucket, a wrong scale) displaces MOST elements by O(lr).
    diffs = np.concatenate([
        np.abs(np.asarray(a) - np.asarray(b)).ravel()
        for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params))
    ])
    assert diffs.max() <= 2 * 5e-2 + 1e-6
    assert np.mean(diffs > 5e-3) < 0.01


def test_overlap_defer_bitexact_vs_eager(model_setup):
    """The production overlap ("defer", double-buffered carry) must be
    bit-identical to the eager per-chunk-reduce spelling — same float
    order, the buffer only changes WHEN each psum is issued."""
    model, state, feats, masks, _ = model_setup
    mesh = make_mesh()
    args = _rl_args(mesh, state, feats, masks)
    outs = {}
    for mode in ("eager", "defer"):
        outs[mode] = make_parallel_rl_update(
            model, mesh, chunks=2, comm=CommConfig(overlap=mode)
        )(*args)
    s_e, m_e = outs["eager"]
    s_d, m_d = outs["defer"]
    assert float(m_e["rl_loss"]) == float(m_d["rl_loss"])
    _assert_trees_bitequal(s_e.params, s_d.params)
    _assert_trees_bitequal(s_e.opt_state, s_d.opt_state)


def test_overlap_close_to_unoverlapped(model_setup):
    """Overlap reduces per chunk instead of accumulate-then-reduce: a
    different float summation order, so parity is tolerance-graded (the
    bit-exact pin for overlap is defer-vs-eager above)."""
    model, state, feats, masks, _ = model_setup
    mesh = make_mesh()
    args = _rl_args(mesh, state, feats, masks)
    s0, m0 = make_parallel_rl_update(
        model, mesh, chunks=2, comm=CommConfig()
    )(*args)
    s1, m1 = make_parallel_rl_update(
        model, mesh, chunks=2, comm=CommConfig(overlap="defer")
    )(*args)
    np.testing.assert_allclose(
        float(m0["rl_loss"]), float(m1["rl_loss"]), rtol=1e-6
    )
    for a, b in zip(jax.tree.leaves(s0.params), jax.tree.leaves(s1.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-5
        )


# ---- construction-time rejection of nonsense combinations -------------------


def test_comm_config_validation():
    with pytest.raises(ValueError, match="comm dtype"):
        CommConfig(dtype="f16")
    with pytest.raises(ValueError, match="overlap"):
        CommConfig(overlap="async")
    with pytest.raises(ValueError, match="bucket_mb"):
        CommConfig(bucket_mb=-1.0)


def test_train_config_validates_comm_knobs():
    with pytest.raises(ValueError, match="comm_dtype"):
        TrainConfig(comm_dtype="f16")
    with pytest.raises(ValueError, match="comm_bucket_mb"):
        TrainConfig(comm_bucket_mb=-2.0)


def test_experiment_config_overlap_needs_chunks():
    with pytest.raises(ValueError, match="update_chunks"):
        ExperimentConfig(train=TrainConfig(comm_overlap=True))
    ExperimentConfig(
        train=TrainConfig(comm_overlap=True),
        rl=RLConfig(update_chunks=5),
    )  # chunks >= 2: fine (5 divides the default num_rollouts=5)


def test_experiment_config_rejects_comm_on_seq_parallel():
    with pytest.raises(ValueError, match="sequence-parallel"):
        ExperimentConfig(
            train=TrainConfig(comm_dtype="bf16"),
            mesh=MeshConfig(seq_devices=2),
        )


def test_factory_rejects_overlap_without_chunks(model_setup):
    model, *_ = model_setup
    with pytest.raises(ValueError, match="chunks"):
        make_parallel_rl_update(
            model, make_mesh(), chunks=1, comm=CommConfig(overlap="defer")
        )


def test_from_train_maps_knobs():
    t = TrainConfig(comm_bucket_mb=2.5, comm_dtype="bf16", comm_overlap=True)
    c = CommConfig.from_train(t)
    assert (c.bucket_mb, c.dtype, c.overlap) == (2.5, "bf16", "defer")
    assert CommConfig.from_train(TrainConfig()).overlap == "off"
