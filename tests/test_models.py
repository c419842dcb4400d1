"""Model layer tests: shapes, unroll consistency, dtype, losses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.losses import (
    masked_cross_entropy,
    reinforce_loss,
    sequence_log_probs,
)
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import shift_right

B, F1, F2, T, V = 3, 5, 4, 7, 23


def tiny_cfg(encoder="temporal_attention", num_layers=1, dtype="float32"):
    return ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 12), ("c3d", 6)),
        d_embed=16,
        d_hidden=16,
        d_att=8,
        encoder=encoder,
        num_layers=num_layers,
        dropout=0.3,
        max_len=T,
        max_frames=F1,
        dtype=dtype,
    )


def make_batch(seed=0):
    rng = np.random.default_rng(seed)
    feats = {
        "resnet": jnp.asarray(rng.normal(size=(B, F1, 12)), jnp.float32),
        "c3d": jnp.asarray(rng.normal(size=(B, F2, 6)), jnp.float32),
    }
    masks = {"c3d": jnp.ones((B, F2), jnp.float32)}
    # per-row frame masks with differing lengths
    m = np.zeros((B, F1), np.float32)
    for i, n in enumerate([3, 5, 2][:B]):
        m[i, :n] = 1
    masks["resnet"] = jnp.asarray(m)
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    return feats, masks, labels


@pytest.mark.parametrize("encoder", ["meanpool", "temporal_attention"])
@pytest.mark.parametrize("num_layers", [1, 2])
def test_forward_shapes(encoder, num_layers):
    cfg = tiny_cfg(encoder, num_layers)
    model = CaptionModel(cfg)
    feats, masks, labels = make_batch()
    params = model.init(jax.random.key(0), feats, masks, labels)
    logits = model.apply(params, feats, masks, labels)
    assert logits.shape == (B, T, V)
    assert logits.dtype == jnp.float32
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    expected_M = 2 if encoder == "meanpool" else F1 + F2
    assert enc.memory.shape == (B, expected_M, cfg.d_embed)
    assert len(enc.carry) == num_layers


@pytest.mark.parametrize("encoder", ["meanpool", "temporal_attention"])
def test_unroll_consistency(encoder):
    """Teacher-forced scan logits == step-by-step decode_step logits."""
    cfg = tiny_cfg(encoder)
    model = CaptionModel(cfg)
    feats, masks, labels = make_batch(1)
    params = model.init(jax.random.key(0), feats, masks, labels)
    logits_scan = model.apply(params, feats, masks, labels)

    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    inputs = shift_right(labels)
    carry = enc.carry
    per_step = []
    for t in range(T):
        carry, lg = model.apply(
            params, carry, inputs[:, t], enc, method=CaptionModel.decode_step
        )
        per_step.append(lg)
    logits_step = jnp.stack(per_step, axis=1)
    np.testing.assert_allclose(logits_scan, logits_step, rtol=1e-5, atol=1e-5)


def test_memory_mask_blocks_padded_frames():
    """Changing features under masked-out frames must not change logits."""
    cfg = tiny_cfg("temporal_attention")
    model = CaptionModel(cfg)
    feats, masks, labels = make_batch(2)
    params = model.init(jax.random.key(0), feats, masks, labels)
    out1 = model.apply(params, feats, masks, labels)
    feats2 = dict(feats)
    noise = np.array(feats["resnet"])
    noise[np.array(masks["resnet"]) == 0] = 99.0
    feats2["resnet"] = jnp.asarray(noise)
    out2 = model.apply(params, feats2, masks, labels)
    np.testing.assert_allclose(out1, out2, rtol=1e-5, atol=1e-5)


def test_dropout_rng_and_determinism():
    cfg = tiny_cfg()
    model = CaptionModel(cfg)
    feats, masks, labels = make_batch(3)
    params = model.init(jax.random.key(0), feats, masks, labels)
    d1 = model.apply(params, feats, masks, labels, train=True,
                     rngs={"dropout": jax.random.key(1)})
    d2 = model.apply(params, feats, masks, labels, train=True,
                     rngs={"dropout": jax.random.key(2)})
    assert not np.allclose(d1, d2)  # dropout active and rng-dependent
    e1 = model.apply(params, feats, masks, labels)
    e2 = model.apply(params, feats, masks, labels)
    np.testing.assert_array_equal(e1, e2)  # eval mode deterministic


def test_bfloat16_compute_path():
    cfg = tiny_cfg(dtype="bfloat16")
    model = CaptionModel(cfg)
    feats, masks, labels = make_batch(4)
    params = model.init(jax.random.key(0), feats, masks, labels)
    # params stay f32, logits come back f32, no NaNs
    flat = jax.tree_util.tree_leaves(params)
    assert all(p.dtype == jnp.float32 for p in flat)
    logits = model.apply(params, feats, masks, labels)
    assert logits.dtype == jnp.float32
    assert np.isfinite(np.array(logits)).all()


def test_shift_right():
    labels = jnp.asarray([[5, 6, 2, 0]], jnp.int32)
    np.testing.assert_array_equal(shift_right(labels), [[BOS_ID, 5, 6, 2]])


# ---- losses ----------------------------------------------------------------


def test_masked_xe_matches_manual():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(2, 3, 5)), jnp.float32)
    labels = jnp.asarray([[1, 2, 0], [3, 2, 4]], jnp.int32)
    mask = jnp.asarray([[1, 1, 0], [1, 1, 1]], jnp.float32)
    got = masked_cross_entropy(logits, labels, mask)
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    manual = 0.0
    for b in range(2):
        for t in range(3):
            if mask[b, t]:
                manual -= logp[b, t, labels[b, t]]
    np.testing.assert_allclose(got, manual / 5.0, rtol=1e-6)


def test_weighted_xe_scales_rows():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(2, 3, 5)), jnp.float32)
    labels = jnp.asarray([[1, 2, 2], [3, 2, 4]], jnp.int32)
    mask = jnp.ones((2, 3), jnp.float32)
    w = jnp.asarray([2.0, 0.0])
    got = masked_cross_entropy(logits, labels, mask, weights=w)
    # only row 0 contributes; weight cancels in numerator/denominator scaling
    row0 = masked_cross_entropy(logits[:1], labels[:1], mask[:1])
    np.testing.assert_allclose(got, row0, rtol=1e-6)


def test_reinforce_loss_sign_and_grad():
    """Positive advantage must push sampled-token logprobs up."""
    logits = jnp.zeros((1, 2, 4), jnp.float32)
    tokens = jnp.asarray([[1, 2]], jnp.int32)
    mask = jnp.ones((1, 2), jnp.float32)

    def loss_fn(lg):
        lp = sequence_log_probs(lg, tokens)
        return reinforce_loss(lp, mask, jnp.asarray([1.0]))

    g = jax.grad(loss_fn)(logits)
    # gradient descent direction increases logprob of sampled tokens
    assert g[0, 0, 1] < 0 and g[0, 1, 2] < 0
    # advantage 0 -> zero gradient
    g0 = jax.grad(
        lambda lg: reinforce_loss(sequence_log_probs(lg, tokens), mask, jnp.asarray([0.0]))
    )(logits)
    np.testing.assert_allclose(g0, 0.0, atol=1e-7)


def test_sequence_log_probs_gather():
    logits = jnp.log(jnp.asarray([[[0.1, 0.2, 0.7]]], jnp.float32))
    lp = sequence_log_probs(logits, jnp.asarray([[2]], jnp.int32))
    np.testing.assert_allclose(lp, np.log(0.7), rtol=1e-4)


# (positions T, the batch's longest caption): every row PAD, one token, and
# around the decode loop's exit granularity (``_exit_stride``: 5 of 30) one
# short of a stride's end, at it, one past it, every position held; and a T
# that ``_exit_stride`` does not divide (31)
_DEPTH_CASES = [(30, 0), (30, 1), (30, 7), (30, 19), (30, 20), (30, 21),
                (30, 30), (31, 9), (31, 31)]
# (vocabulary, rows, compute dtype): the module's batch; and many rows over
# three ordinary tokens, so that rows share an input token at a position
# (all of them at position 0, BOS) and along positions: the word embedding's
# gradient is a sum of many input cotangents a table row
_FEW_TOKENS = [(7, 12, "float32"), (7, 12, "bfloat16")]
_TEACHER_FORCE_CASES = [
    (encoder, length, depth, V, B, "float32")
    for length, depth in _DEPTH_CASES
    for encoder in ("temporal_attention", "meanpool")
] + [
    (encoder, length, depth, *few)
    for encoder, (length, depth) in (("temporal_attention", (30, 19)),
                                     ("meanpool", (31, 9)))
    for few in _FEW_TOKENS
]


def _teacher_force_setup(encoder, length, depth, vocab, rows, dtype,
                         d_embed=16):
    """``(model, params, enc, labels)``: ``rows`` left-aligned captions over
    ``vocab`` tokens, PAD after the end, row 0 the longest (``depth``)."""
    cfg = dataclasses.replace(tiny_cfg(encoder=encoder, dtype=dtype),
                              vocab_size=vocab, d_embed=d_embed)
    model = CaptionModel(cfg)
    feats, masks, _ = make_batch(3)
    tile = lambda x: jnp.tile(x, (rows // B,) + (1,) * (x.ndim - 1))  # noqa: E731
    feats, masks = jax.tree.map(tile, (feats, masks))
    rng = np.random.default_rng(depth)
    lens = rng.integers(0, depth + 1, size=(rows, 1))
    lens[0] = depth
    labels = jnp.asarray(
        np.where(np.arange(length) < lens,
                 rng.integers(4, vocab, size=(rows, length)), 0), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    return model, params, enc, labels


def _full_logps(model, labels):
    return lambda p, e: sequence_log_probs(
        model.apply(p, e, labels, method=CaptionModel.decode_logits), labels
    )


def _lean_logps(model, labels):
    return lambda p, e: model.apply(
        p, e, labels, method=CaptionModel.teacher_force_logps
    )


def _masked_sum(f, labels):
    mask = (labels != 0).astype(jnp.float32)
    return lambda p, e: jnp.sum(f(p, e) * mask)


@pytest.mark.parametrize("encoder,length,depth,vocab,rows,dtype",
                         _TEACHER_FORCE_CASES)
def test_teacher_force_logps_matches_full_logits(encoder, length, depth,
                                                 vocab, rows, dtype):
    """The target-logp path (the RL update's form) bounds both its passes
    by the batch's longest caption: at every position up to it the values
    equal gather(log_softmax(decode_logits)), past it they are 0.0 (what
    the token mask makes of them anyway), and the gradients of the masked
    sum w.r.t. the parameters and the encoder output are the full scan's.

    The word embedding's gradient is summed once after the backward loop,
    every addition in f32. In float32 it is held to the full scan's like
    every other leaf; in bfloat16, where the full scan adds the rows of a
    position that share a token in bfloat16, it is held to the float32 full
    scan at least as tightly as the bfloat16 full scan is."""
    from cst_captioning_tpu.models.captioner import scan_positions

    model, params, enc, labels = _teacher_force_setup(
        encoder, length, depth, vocab, rows, dtype)
    full, lean = _full_logps(model, labels), _lean_logps(model, labels)
    exact = dtype == "float32"
    close = dict(rtol=1e-6, atol=1e-6) if exact else dict(rtol=0, atol=2e-2)

    want, got = np.asarray(full(params, enc)), np.asarray(lean(params, enc))
    np.testing.assert_allclose(got[:, :depth], want[:, :depth], **close)
    np.testing.assert_array_equal(got[:, depth:], 0.0)
    assert list(np.asarray(scan_positions(labels))) == [depth, length]

    grad = lambda f: jax.grad(  # noqa: E731
        _masked_sum(f, labels), argnums=(0, 1))(params, enc)
    g_want, g_got = grad(full), grad(lean)
    if exact:
        for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        return
    # bfloat16: every leaf's norm within 2 % of the full scan's, and the
    # embedding against the same tree's float32 full scan
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        np.testing.assert_allclose(
            np.linalg.norm(np.asarray(a, np.float32)),
            np.linalg.norm(np.asarray(b, np.float32)), rtol=2e-2, atol=1e-6)
    model32, _, enc32, _ = _teacher_force_setup(
        encoder, length, depth, vocab, rows, "float32")
    table = lambda g: np.asarray(  # noqa: E731
        g[0]["params"]["cell"]["word_embed"]["embedding"], np.float64)
    ref = table(jax.grad(_masked_sum(_full_logps(model32, labels), labels),
                         argnums=(0, 1))(params, enc32))
    gap = lambda g: np.linalg.norm(table(g) - ref) / np.linalg.norm(ref)  # noqa: E731
    assert 0 < gap(g_got) <= gap(g_want) < 1e-2, (gap(g_got), gap(g_want))


def _while_bodies(text: str) -> list[str]:
    """The text of every ``stablehlo.while`` of a lowered module, condition
    and body, nested loops inside their parents and once more by
    themselves."""
    bodies = []
    for at in [i for i in range(len(text))
               if text.startswith("stablehlo.while", i)]:
        depth, i, opened = 0, at, False
        while True:
            c = text[i]
            depth += (c == "{") - (c == "}")
            opened = opened or c == "{"
            i += 1
            # the condition's region closes, then `do {` opens the body
            if opened and depth == 0 and not text[i:i + 6].lstrip(
                    ).startswith("do"):
                break
        bodies.append(text[at:i])
    return bodies


def test_teacher_force_loops_hold_no_vocabulary_table():
    """Neither time loop of teacher forcing touches an array with the word
    embedding's shape, forward or backward: the rows are looked up before
    the forward loop and their cotangents summed into the table after the
    backward loop. Read from the lowered text of the masked sum's gradient;
    d_embed is chosen apart from every other width."""
    vocab, d_embed = 29, 24
    model, params, enc, labels = _teacher_force_setup(
        "temporal_attention", 30, 19, vocab, 6, "bfloat16", d_embed=d_embed)
    text = jax.jit(jax.grad(
        _masked_sum(_lean_logps(model, labels), labels), argnums=(0, 1)
    )).lower(params, enc).as_text()
    table = f"tensor<{vocab}x{d_embed}x"
    assert table in text            # the leaf and its gradient are there
    loops = _while_bodies(text)
    assert len(loops) == 2          # forward and backward, no loop nested
    for body in loops:
        assert f"x{vocab}x" in body or f"x{vocab}>" in body  # out_proj's
        assert table not in body
    # the full scan's loops, which embed a position inside the step, do
    # hold it: the check can fail
    text = jax.jit(jax.grad(
        _masked_sum(_full_logps(model, labels), labels), argnums=(0, 1)
    )).lower(params, enc).as_text()
    assert any(table in body for body in _while_bodies(text))


def test_teacher_force_embedding_gradient_on_a_data_mesh():
    """The word embedding's gradient summed under ``shard_map`` over four
    devices on the update's data axis (each shard its own rows, its own
    depth, its own sum after its own loop; the shards' sums added by the
    one reduction the update makes) equals the one-device gradient."""
    from jax.sharding import Mesh, PartitionSpec as P

    model, params, enc, labels = _teacher_force_setup(
        "temporal_attention", 30, 19, 7, 12, "float32")
    mask = (labels != 0).astype(jnp.float32)

    def masked(p, e, lab, m):
        return jnp.sum(_lean_logps(model, lab)(p, e) * m)

    want = jax.grad(masked)(params, enc, labels, mask)
    mesh = Mesh(np.asarray(jax.devices()[:4]), ("data",))

    def shard(p, e, lab, m):
        # the update's own spelling (rl/scst.py): per-shard local gradients
        # of parameters typed varying, then one psum
        p = jax.tree.map(lambda x: jax.lax.pcast(x, "data", to="varying"), p)
        return jax.lax.psum(jax.grad(masked)(p, e, lab, m), "data")

    got = jax.jit(jax.shard_map(
        shard, mesh=mesh, in_specs=(P(), P("data"), P("data"), P("data")),
        out_specs=P(),
    ))(params, enc, labels, mask)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-6)
    table = lambda g: g["params"]["cell"]["word_embed"]["embedding"]  # noqa: E731
    assert float(jnp.abs(table(want)).max()) > 0
