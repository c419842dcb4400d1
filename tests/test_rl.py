"""RL tests: consensus rewards, SCB baseline, SCST learning on a rigged reward."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.config.config import EOS_ID, ModelConfig, RLConfig, TrainConfig
from cst_captioning_tpu.data.vocab import Vocab
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.rl import (
    RewardComputer,
    SCSTTrainer,
    make_parallel_rl_update,
    make_rl_update,
    scb_baseline,
)
from cst_captioning_tpu.train import create_train_state, make_mesh, make_optimizer, replicate, shard_batch

V = 14
WORDS = [f"w{i}" for i in range(V - 4)]


def make_vocab():
    return Vocab.from_corpus_words(WORDS)


def test_reward_computer_prefers_matching_captions():
    vocab = make_vocab()
    gts = {"v0": ["w0 w1 w2", "w0 w1 w3"], "v1": ["w5 w6", "w5 w6 w7"]}
    rc = RewardComputer(vocab, gts)
    rows = np.asarray(
        [
            vocab.encode("w0 w1 w2".split()) + [EOS_ID],
            vocab.encode("w5 w6 w7".split()) + [EOS_ID],
        ],
        np.int32,
    )
    r = rc(["v0", "v1"], rows)
    assert r.shape == (2,) and (r > 0).all()
    # swapping hyps across videos must tank the reward
    r_swapped = rc(["v1", "v0"], rows)
    assert r_swapped[0] < r[0] and r_swapped[1] < r[1]


def test_reward_computer_rollout_major_cycling():
    vocab = make_vocab()
    gts = {"v0": ["w0 w1"], "v1": ["w5 w6"]}
    rc = RewardComputer(vocab, gts)
    row_v0 = vocab.encode(["w0", "w1"]) + [EOS_ID]
    row_v1 = vocab.encode(["w5", "w6"]) + [EOS_ID]
    # K=2 rollouts, B=2: rows [r0v0, r0v1, r1v0, r1v1]
    rows = np.asarray([row_v0, row_v1, row_v0, row_v1], np.int32)
    r = rc(["v0", "v1"], rows)
    assert r[0] == pytest.approx(r[2]) and r[1] == pytest.approx(r[3])
    assert (r > 0).all()


def test_reward_computer_bleu_mix_changes_scores():
    vocab = make_vocab()
    gts = {"v0": ["w0 w1 w2 w3 w4"]}
    rc_c = RewardComputer(vocab, gts, cider_weight=1.0, bleu_weight=0.0)
    rc_m = RewardComputer(vocab, gts, cider_weight=1.0, bleu_weight=0.5)
    row = np.asarray([vocab.encode("w0 w1 w2 w3 w4".split()) + [EOS_ID]], np.int32)
    assert rc_m(["v0"], row)[0] > rc_c(["v0"], row)[0]


def test_reward_empty_hypothesis_is_zero():
    vocab = make_vocab()
    rc = RewardComputer(vocab, {"v0": ["w0 w1"]})
    r = rc(["v0"], np.zeros((1, 5), np.int32))  # all PAD
    assert r[0] == 0.0


def test_scb_baseline_leave_one_out():
    r = np.asarray([[1.0, 2.0], [3.0, 4.0], [5.0, 6.0]])  # [K=3, B=2]
    b = scb_baseline(r)
    np.testing.assert_allclose(b[0], [(3 + 5) / 2, (4 + 6) / 2])
    np.testing.assert_allclose(b[1], [(1 + 5) / 2, (2 + 6) / 2])
    # K=1 -> zero baseline
    np.testing.assert_allclose(scb_baseline(np.ones((1, 4))), 0.0)


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
    return model, state, feats, masks


class TokenReward:
    """Rigged reward: +1 per occurrence of a target token (RewardComputer API)."""

    def __init__(self, target: int):
        self.target = target

    def __call__(self, video_ids, rows):
        return (np.asarray(rows) == self.target).sum(axis=1).astype(np.float32)


@pytest.mark.parametrize("baseline", ["greedy", "scb", "none"])
def test_scst_learns_rigged_reward(model_setup, baseline):
    """A few SCST steps must raise the frequency of the rewarded token."""
    model, state, feats, masks = model_setup
    cfg = RLConfig(enabled=True, num_rollouts=4, baseline=baseline, temperature=1.0)
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg)
    vids = [f"v{i}" for i in range(8)]
    rng = jax.random.key(0)
    rewards = []
    for i in range(15):
        rng, step_rng = jax.random.split(rng)
        state, m = trainer.train_step(state, feats, masks, vids, step_rng)
        rewards.append(m["reward_mean"])
    assert rewards[-1] > rewards[0] + 0.5, f"{baseline}: {rewards[0]:.2f}->{rewards[-1]:.2f}"


def test_parallel_rl_update_matches_single(model_setup):
    model, state, feats, masks = model_setup
    mesh = make_mesh()
    K, B, T = 3, 8, 5
    rng = np.random.default_rng(3)
    samples = jnp.asarray(rng.integers(2, V, size=(K, B, T)), jnp.int32)
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)

    valid = jnp.ones((B,), jnp.float32)
    s_state, s_m = make_rl_update(model)(state, feats, masks, samples, adv, valid)
    p_state, p_m = make_parallel_rl_update(model, mesh)(
        replicate(mesh, state),
        *shard_batch(mesh, (feats, masks)),
        jax.device_put(samples, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(None, "data"))),
        jax.device_put(adv, jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec(None, "data"))),
        shard_batch(mesh, valid),
    )
    np.testing.assert_allclose(float(s_m["rl_loss"]), float(p_m["rl_loss"]), rtol=1e-5)
    # the gradient's scale too — Adam hides a doubly-reduced (8x) gradient
    np.testing.assert_allclose(
        float(s_m["grad_norm"]), float(p_m["grad_norm"]), rtol=1e-4
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(s_state.params),
        jax.tree_util.tree_leaves(p_state.params),
    ):
        # lr=5e-2 + Adam rsqrt amplifies psum float reassociation; a real
        # normalization bug would be O(1) off, so 1e-2 still discriminates
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-2, atol=2e-3)


@pytest.mark.parametrize("chunks", [3, 1])
def test_chunked_rl_update_matches_fused(model_setup, chunks):
    """Gradient accumulation over the rollout axis (rl.update_chunks — the
    HBM headroom lever, VERDICT r2 next #3) produces the same loss and
    post-update params as the fused update, single-device AND sharded."""
    model, state, feats, masks = model_setup
    K, B, T = 3, 8, 5
    rng = np.random.default_rng(4)
    samples = jnp.asarray(rng.integers(2, V, size=(K, B, T)), jnp.int32)
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.asarray([1, 1, 1, 1, 1, 1, 0, 0], jnp.float32)

    f_state, f_m = make_rl_update(model)(state, feats, masks, samples, adv, valid)
    c_state, c_m = make_rl_update(model, chunks=chunks)(
        state, feats, masks, samples, adv, valid
    )
    np.testing.assert_allclose(
        float(f_m["rl_loss"]), float(c_m["rl_loss"]), rtol=1e-6
    )
    for a, b in zip(
        jax.tree_util.tree_leaves(f_state.params),
        jax.tree_util.tree_leaves(c_state.params),
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )

    if chunks > 1:
        mesh = make_mesh()
        sp = jax.sharding.PartitionSpec
        kb = jax.sharding.NamedSharding(mesh, sp(None, "data"))
        p_state, p_m = make_parallel_rl_update(model, mesh, chunks=chunks)(
            replicate(mesh, state),
            *shard_batch(mesh, (feats, masks)),
            jax.device_put(samples, kb),
            jax.device_put(adv, kb),
            shard_batch(mesh, valid),
        )
        np.testing.assert_allclose(
            float(f_m["rl_loss"]), float(p_m["rl_loss"]), rtol=1e-5
        )
        np.testing.assert_allclose(
            float(f_m["grad_norm"]), float(p_m["grad_norm"]), rtol=1e-4
        )
        for a, b in zip(
            jax.tree_util.tree_leaves(f_state.params),
            jax.tree_util.tree_leaves(p_state.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-2, atol=2e-3
            )

    with pytest.raises(ValueError, match="must divide"):
        make_rl_update(model, chunks=2)(state, feats, masks, samples, adv, valid)


@pytest.mark.parametrize("rows,cap,block", [
    (1792, 448, 448),    # the one-chip cell: four blocks
    (1792, 896, 896),
    (1792, 300, 256),    # the largest divisor under the cap
    (448, 448, 448),     # a shard of four chips: at the cap, not cut
    (1137, 448, 379),
    (898, 448, 898),     # 2 x 449: no divisor in (224, 448], not cut
    (7, 3, 7),
])
def test_row_block_is_an_equal_divisor_under_the_cap(rows, cap, block):
    from cst_captioning_tpu.rl.scst import _row_block

    assert _row_block(rows, cap) == block
    assert rows % block == 0 and (block == rows or cap // 2 < block <= cap)


# (rows B, cap on a block's rows, valid mask, devices of the mesh or 0)
_WRAP_PADDED = [1, 1, 1, 1, 1, 0, 0, 0]
_ROW_BLOCK_CASES = {
    "blocks_of_2": (8, 2, [1] * 8, 0),
    "blocks_of_4": (8, 4, [1] * 8, 0),
    # the valid rows end inside a block, and a whole block is padding
    "invalid_rows_across_a_boundary": (8, 2, _WRAP_PADDED, 0),
    "sharded_2_devices_blocks_of_2": (8, 2, _WRAP_PADDED, 2),
    # not cut: bit-identical to the program without row blocks
    "rows_at_the_cap": (8, 8, _WRAP_PADDED, 0),
    "no_divisor_under_the_cap": (7, 3, _WRAP_PADDED[1:], 0),
    "sharded_rows_under_the_cap": (8, 4, _WRAP_PADDED, 2),
}


@pytest.mark.parametrize("case", list(_ROW_BLOCK_CASES))
def test_row_blocked_rl_update_matches_unblocked(model_setup, monkeypatch, case):
    """The chunked update cut into row blocks (rl/scst._chunked_loss_grads:
    the block comes from the shape and a module constant, shrunk here so
    that a tiny batch is cut) gives the loss and the post-update parameters
    of the uncut update to f32 summation order, on one device and inside
    shard_map; where the shape is not cut the program is the uncut one,
    text and results."""
    from cst_captioning_tpu.rl import scst

    model, state, feats, masks = model_setup
    B, cap, valid, devices = _ROW_BLOCK_CASES[case]
    K, T, chunks = 3, 5, 3
    rng = np.random.default_rng(6)
    samples = jnp.asarray(rng.integers(2, V, size=(K, B, T)), jnp.int32)
    adv = jnp.asarray(rng.normal(size=(K, B)), jnp.float32)
    valid = jnp.asarray(valid, jnp.float32)
    feats, masks = jax.tree.map(lambda x: x[:B], (feats, masks))
    args = (state, feats, masks, samples, adv, valid)
    if devices:
        mesh = make_mesh(devices)
        kb = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "data")
        )
        args = (
            replicate(mesh, state), *shard_batch(mesh, (feats, masks)),
            jax.device_put(samples, kb), jax.device_put(adv, kb),
            shard_batch(mesh, valid),
        )
        build = lambda: make_parallel_rl_update(model, mesh, chunks=chunks)
    else:
        build = lambda: make_rl_update(model, chunks=chunks)

    uncut = build()            # the cap as shipped: far above 8 rows
    u_state, u_m = uncut(*args)
    uncut_text = uncut.lower(*args).as_text()
    monkeypatch.setattr(scst, "_ROW_BLOCK_CAP", cap)
    cut = build()
    c_state, c_m = cut(*args)

    local_rows = B // max(devices, 1)
    block = scst._row_block(local_rows, cap)
    # the gauges say what the trace of `cut` chose
    assert obs.gauge("rl.update.block_rows").value == block
    assert obs.gauge("rl.update.row_blocks").value == local_rows // block
    same_text = uncut_text == cut.lower(*args).as_text()
    if block == local_rows:
        assert same_text
        assert float(u_m["rl_loss"]) == float(c_m["rl_loss"])
        for a, b in zip(jax.tree.leaves(u_state.params),
                        jax.tree.leaves(c_state.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        return
    assert not same_text
    np.testing.assert_allclose(
        float(u_m["rl_loss"]), float(c_m["rl_loss"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(u_m["grad_norm"]), float(c_m["grad_norm"]), rtol=1e-5
    )
    for a, b in zip(jax.tree.leaves(u_state.params),
                    jax.tree.leaves(c_state.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )


def _padded_samples(lens, T, seed=7):
    """[K, B] caption lengths -> left-aligned [K, B, T] tokens, PAD after."""
    lens = np.asarray(lens)
    tokens = np.random.default_rng(seed).integers(2, V, size=lens.shape + (T,))
    return jnp.asarray(
        np.where(np.arange(T) < lens[..., None], tokens, 0), jnp.int32
    )


def _full_scan_logps(self, enc, labels, train=False):
    """The teacher forcing the update ran before its scan was bounded:
    every one of the T positions, through the materialised logits."""
    from cst_captioning_tpu.losses import sequence_log_probs

    return sequence_log_probs(self.decode_logits(enc, labels, train), labels)


# (update_chunks, cap on a block's rows or 0, devices of the mesh or 0)
_BOUNDED_SCAN_CASES = {
    "one_device_unchunked": (1, 0, 0),
    "one_device_chunked": (3, 0, 0),
    "row_blocks_of_2": (3, 2, 0),
    "shard_map_2_devices": (3, 0, 2),
    "shard_map_4_devices": (3, 0, 4),
    "shard_map_2_devices_row_blocks": (3, 2, 2),
    "shard_map_4_devices_unchunked": (1, 0, 4),
}


@pytest.mark.parametrize("case", list(_BOUNDED_SCAN_CASES))
def test_bounded_scan_rl_update_matches_full_scan(model_setup, monkeypatch,
                                                  case):
    """One update on samples padded to T (teacher forcing runs only the
    positions up to the longest caption of the rows it is given:
    models/captioner.py)
    gives the loss and the post-update parameters of the same samples
    through the full scan, kept here as the reference: on one device, cut
    into row blocks, and inside shard_map where every shard, chunk and
    block has another depth."""
    from cst_captioning_tpu.rl import scst

    model, state, feats, masks = model_setup
    chunks, cap, devices = _BOUNDED_SCAN_CASES[case]
    K, B, T = 3, 8, 12
    # longest caption by clip, another in every shard of 2 and of 4, every
    # rollout a little shorter than the one before
    longest = np.asarray([2, 1, 11, 6, 4, 3, 8, 12])
    lens = np.stack([np.maximum(longest - k, 0) for k in range(K)])
    samples = _padded_samples(lens, T)
    adv = jnp.asarray(np.random.default_rng(8).normal(size=(K, B)),
                      jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    args = (state, feats, masks, samples, adv, valid)
    if devices:
        mesh = make_mesh(devices)
        kb = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "data")
        )
        args = (
            replicate(mesh, state), *shard_batch(mesh, (feats, masks)),
            jax.device_put(samples, kb), jax.device_put(adv, kb),
            shard_batch(mesh, valid),
        )
        build = lambda: make_parallel_rl_update(model, mesh, chunks=chunks)
    else:
        build = lambda: make_rl_update(model, chunks=chunks)
    if cap:
        monkeypatch.setattr(scst, "_ROW_BLOCK_CAP", cap)

    b_state, b_m = build()(*args)
    with monkeypatch.context() as patch:
        patch.setattr(CaptionModel, "teacher_force_logps", _full_scan_logps)
        f_state, f_m = build()(*args)

    np.testing.assert_allclose(
        float(b_m["rl_loss"]), float(f_m["rl_loss"]), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(b_m["grad_norm"]), float(f_m["grad_norm"]), rtol=1e-5
    )
    for a, b in zip(jax.tree.leaves(b_state.params),
                    jax.tree.leaves(f_state.params)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-6
        )
    # the tally beside the metrics, worked out by hand: a scan for every
    # rollout chunk of every row block of every shard, each run up to the
    # longest caption it holds
    rows = B // max(devices, 1)
    block = scst._row_block(rows, max(cap // (K // chunks), 1)) if (
        cap and chunks > 1) else rows
    run = sum(
        lens[k:k + K // chunks, b:b + block].max()
        for k in range(0, K, K // chunks) for b in range(0, B, block)
    )
    assert int(b_m["positions_run"]) == run
    assert int(b_m["positions"]) == chunks * (B // block) * T


@pytest.mark.parametrize("devices", [0, 2])
def test_bounded_scan_compiles_once_for_every_depth(model_setup, devices):
    """The depth is data: updates on samples whose longest caption is 5, 12
    and 30 of T = 30 leave the jitted update with one compiled entry."""
    model, state, feats, masks = model_setup
    K, B, T = 3, 8, 30
    adv = jnp.ones((K, B), jnp.float32)
    valid = jnp.ones((B,), jnp.float32)
    if devices:
        mesh = make_mesh(devices)
        kb = jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(None, "data")
        )
        place = lambda x: jax.device_put(x, kb)  # noqa: E731
        state = replicate(mesh, state)
        feats, masks, valid = shard_batch(mesh, (feats, masks, valid))
        adv = place(adv)
        update = make_parallel_rl_update(model, mesh, chunks=3)
    else:
        place = lambda x: x  # noqa: E731
        update = make_rl_update(model, chunks=3)
    ran = []
    for depth in (5, 12, 30):
        lens = np.full((K, B), 3)
        lens[1, 5] = depth
        _, m = update(state, feats, masks,
                      place(_padded_samples(lens, T)), adv, valid)
        ran.append(int(m["positions_run"]))
        assert int(m["positions"]) == 3 * max(devices, 1) * T
    assert update._cache_size() == 1
    # every scan runs its 3 positions, the one that holds the long caption
    # runs up to it
    others = 3 * max(devices, 1) - 1
    assert ran == [others * 3 + 5, others * 3 + 12, others * 3 + 30]


def test_update_positions_counter_and_report(model_setup, tmp_path):
    """``SCSTTrainer.observe_update_positions`` turns the scalars the
    updates returned beside their metrics into the counters
    ``rl.update.positions.run`` / ``rl.update.positions``, and
    ``cli.obs_report`` prints their share on its ``update row blocks:``
    line; samples that fill all T positions read a share of 1."""
    from cst_captioning_tpu.obs.report import build_report, render_report

    model, state, feats, masks = model_setup
    K, B, T = 2, 8, 10
    cfg = RLConfig(enabled=True, num_rollouts=K, baseline="none",
                   update_chunks=2)
    obs.REGISTRY.reset()
    obs.configure(str(tmp_path / "obs"), run="positions")
    try:
        trainer = SCSTTrainer(model, TokenReward(target=7), cfg, max_len=T)
        host = {"reward_mean": 0.0}
        ones = np.ones((B,), np.float32)
        adv = np.ones((K, B), np.float32)

        def apply(lens):
            return trainer._apply(state, adv, host,
                                  _padded_samples(lens, T), feats, masks, ones)

        # one scan a rollout chunk: the first chunk's longest caption is 7,
        # the second's 3
        lens = np.full((K, B), 2)
        lens[0, 3], lens[1, 6] = 7, 3
        _, m = apply(lens)
        assert "positions_run" in m and "rl_loss" in m
        snap = obs.snapshot()["counters"]
        assert "rl.update.positions" not in snap    # nothing read in _apply
        trainer.observe_update_positions()
        snap = obs.snapshot()["counters"]
        assert snap["rl.update.positions.run"] == 7 + 3
        assert snap["rl.update.positions"] == 2 * T
        # a second update, every position held: its share is 1
        apply(np.full((K, B), T))
        trainer.observe_update_positions()
        trainer.observe_update_positions()          # nothing pending: no-op
        snap = obs.snapshot()
        assert snap["counters"]["rl.update.positions.run"] == 10 + 2 * T
        assert snap["counters"]["rl.update.positions"] == 4 * T
        events = [
            {"ts": 0.0, "event": "run_start", "run": "positions",
             "thread": "main"},
            {"ts": 1.0, "event": "metrics", "histograms": {},
             "counters": snap["counters"], "gauges": snap["gauges"]},
            {"ts": 2.0, "event": "run_end", "run": "positions"},
        ]
        rep = build_report(events)
        assert rep["update"]["positions_run_share"] == pytest.approx(30 / 40)
        assert ("update row blocks: 1 block(s) of 8 row(s) a rollout chunk "
                "and device; their scans ran 30 of 40 position(s) (75.0%"
                in render_report(rep))
    finally:
        obs.shutdown()
        obs.REGISTRY.reset()


def test_train_step_zero_weights_invalid_rows(model_setup):
    """Wrap-padded rows (valid=False) must not change the update."""
    model, state, feats, masks = model_setup
    cfg = RLConfig(enabled=True, num_rollouts=2, baseline="none")
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg)
    vids = [f"v{i}" for i in range(8)]
    rng = jax.random.key(5)
    valid = np.asarray([1, 1, 1, 1, 0, 0, 0, 0], np.float32)
    s1, m1 = trainer.train_step(state, feats, masks, vids, rng, valid=valid)
    # metrics only reflect valid rows
    rows_r = TokenReward(7)(vids, np.zeros((16, 5)))
    assert np.isfinite(m1["reward_mean"])
    # gradient from invalid rows is excluded: corrupting their features
    # must not change the resulting params
    feats2 = {k: v.at[4:].set(99.0) for k, v in feats.items()}
    s2, m2 = trainer.train_step(state, feats2, masks, vids, rng, valid=valid)
    for a, b in zip(
        jax.tree_util.tree_leaves(s1.params), jax.tree_util.tree_leaves(s2.params)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def _reward_computer(vocab, gts, native: bool, **kw) -> RewardComputer:
    """Build a RewardComputer pinned to one scoring path.

    ``native=True`` REQUIRES the C++ kernel: if it failed to load the parity
    test would silently compare Python against itself, so skip loudly instead
    (VERDICT r1 weak #4).
    """
    rc = RewardComputer(vocab, gts, use_native=native, **kw)
    if native and rc._native is not True:
        pytest.skip("C++ creward kernel unavailable (no g++?): native parity "
                    "path cannot be exercised")
    if not native:
        assert rc._native is None
    return rc


@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_fast_reward_matches_cider_oracle(native):
    """Cached-ref reward path must reproduce metrics.cider.CiderD exactly."""
    from cst_captioning_tpu.metrics.cider import CiderD, CorpusDF

    rng = np.random.default_rng(0)
    vocab = make_vocab()
    vids = [f"v{i}" for i in range(6)]
    gts = {
        v: [
            " ".join(rng.choice(WORDS, size=rng.integers(3, 9)))
            for _ in range(4)
        ]
        for v in vids
    }
    refs = {v: [c.split() for c in caps] for v, caps in gts.items()}
    df = CorpusDF.from_refs(list(refs.values()))
    rc = _reward_computer(vocab, gts, native, df=df, cider_weight=1.0,
                          bleu_weight=0.0)

    rows = np.asarray(
        [
            vocab.encode(list(rng.choice(WORDS, size=rng.integers(2, 8)))) + [EOS_ID]
            + [0] * 10
            for _ in range(12)
        ][0:12],
        dtype=object,
    )
    rows = np.stack([np.asarray((list(r) + [0] * 12)[:12], np.int32) for r in rows])
    got = rc(vids, rows)

    oracle = CiderD(df=df)
    hyps = [vocab.decode(r).split() for r in rows]
    o_gts = {str(i): refs[vids[i % 6]] for i in range(12)}
    o_res = {str(i): [hyps[i]] for i in range(12)}
    _, want = oracle.compute_score(o_gts, o_res)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-8)


@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_fast_reward_matches_bleu_oracle(native):
    from cst_captioning_tpu.metrics.bleu import Bleu
    from cst_captioning_tpu.metrics.cider import CorpusDF

    rng = np.random.default_rng(1)
    vocab = make_vocab()
    vids = ["a", "b"]
    gts = {
        v: [" ".join(rng.choice(WORDS, size=rng.integers(4, 9))) for _ in range(3)]
        for v in vids
    }
    refs = {v: [c.split() for c in caps] for v, caps in gts.items()}
    df = CorpusDF.from_refs(list(refs.values()))
    rc_mixed = _reward_computer(vocab, gts, native, df=df, cider_weight=0.0,
                                bleu_weight=1.0)
    rows = np.stack(
        [
            np.asarray(
                (vocab.encode(list(rng.choice(WORDS, size=6))) + [EOS_ID] + [0] * 10)[:10],
                np.int32,
            )
            for _ in range(8)
        ]
    )
    got = rc_mixed(vids, rows)
    oracle = Bleu(4)
    for i in range(8):
        hyp = vocab.decode(rows[i]).split()
        want = oracle.sentence_bleu(hyp, refs[vids[i % 2]])[3] * 10.0
        np.testing.assert_allclose(got[i], want, rtol=1e-6)


def test_parallel_rl_decode_greedy_matches_single(model_setup):
    """Sharded decode must produce the single-device greedy tokens exactly."""
    from cst_captioning_tpu.rl import make_parallel_rl_decode, make_rl_decode

    model, state, feats, masks = model_setup
    mesh = make_mesh()
    K, T = 3, 5
    rng = jax.random.key(11)
    g_single, s_single = make_rl_decode(model, K, max_len=T)(
        state.params, feats, masks, rng
    )
    pdec = make_parallel_rl_decode(model, mesh, K, max_len=T)
    g_par, s_par = pdec(
        replicate(mesh, state).params, *shard_batch(mesh, (feats, masks)), rng
    )
    # greedy is deterministic: sharded == concatenated single-device decode
    np.testing.assert_array_equal(np.asarray(g_par), np.asarray(g_single))
    # samples: same static shape, valid token range, PAD-after-EOS invariant
    assert s_par.shape == s_single.shape == (K, 8, T)
    s = np.asarray(s_par)
    assert (s >= 0).all() and (s < V).all()
    from cst_captioning_tpu.config.config import PAD_ID

    for row in s.reshape(-1, T):
        eos = np.where(row == EOS_ID)[0]
        if eos.size:
            assert (row[eos[0] + 1 :] == PAD_ID).all()


def test_rl_decode_fused_matches_two_loop(model_setup):
    """make_rl_decode's fused one-loop default is bit-exact vs the two-loop
    reference (the PR-4 acceptance pin) in its greedy lane, and in its
    sampled lanes vs the Gumbel-max loop whose key stream the fused family
    keeps (tests/_gumbel_sample.py); the two-loop side's samples are
    ``sample_decode``'s own, one uniform a lane. Fixed rng."""
    from _gumbel_sample import gumbel_sample_decode
    from cst_captioning_tpu.decoding import sample_decode
    from cst_captioning_tpu.rl import make_rl_decode

    model, state, feats, masks = model_setup
    K, T = 3, 5
    rng = jax.random.key(17)
    g_two, s_two = make_rl_decode(model, K, max_len=T, fused=False)(
        state.params, feats, masks, rng
    )
    g_one, s_one = make_rl_decode(model, K, max_len=T, fused=True)(
        state.params, feats, masks, rng
    )
    np.testing.assert_array_equal(np.asarray(g_one), np.asarray(g_two))
    s_gumbel, _ = gumbel_sample_decode(
        model, state.params, feats, masks, rng, num_rollouts=K, max_len=T
    )
    np.testing.assert_array_equal(np.asarray(s_one), np.asarray(s_gumbel))
    s_plain, _ = sample_decode(
        model, state.params, feats, masks, rng, num_rollouts=K, max_len=T
    )
    np.testing.assert_array_equal(np.asarray(s_two), np.asarray(s_plain))


def test_parallel_rl_decode_fused_matches_two_loop(model_setup):
    """The sharded (batch_axes) fused decode is bit-exact vs the sharded
    two-loop reference in its greedy lane. A decode has no cross-example
    interaction, so shard ``i``'s samples are those of its own rows under
    ``fold_in(rng, i)``: the fused side's by the Gumbel-max loop
    (tests/_gumbel_sample.py), the two-loop side's by ``sample_decode``,
    which pins the ``axis_index`` fold for both families."""
    from _gumbel_sample import gumbel_sample_decode
    from cst_captioning_tpu.decoding import sample_decode
    from cst_captioning_tpu.rl import make_parallel_rl_decode

    model, state, feats, masks = model_setup
    mesh = make_mesh()
    K, T = 3, 5
    rng = jax.random.key(19)
    state_r = replicate(mesh, state)
    f_s, m_s = shard_batch(mesh, (feats, masks))
    g_two, s_two = make_parallel_rl_decode(model, mesh, K, max_len=T,
                                           fused=False)(
        state_r.params, f_s, m_s, rng
    )
    g_one, s_one = make_parallel_rl_decode(model, mesh, K, max_len=T,
                                           fused=True)(
        state_r.params, f_s, m_s, rng
    )
    np.testing.assert_array_equal(np.asarray(g_one), np.asarray(g_two))
    shards = mesh.devices.size
    rows = next(iter(feats.values())).shape[0] // shards
    for i in range(shards):
        own = slice(i * rows, (i + 1) * rows)
        f_i = {n: v[own] for n, v in feats.items()}
        m_i = {n: v[own] for n, v in masks.items()}
        rng_i = jax.random.fold_in(rng, i)
        s_gumbel, _ = gumbel_sample_decode(
            model, state.params, f_i, m_i, rng_i, num_rollouts=K, max_len=T
        )
        np.testing.assert_array_equal(
            np.asarray(s_one)[:, own], np.asarray(s_gumbel))
        s_plain, _ = sample_decode(
            model, state.params, f_i, m_i, rng_i, num_rollouts=K, max_len=T
        )
        np.testing.assert_array_equal(
            np.asarray(s_two)[:, own], np.asarray(s_plain))


def test_train_epoch_pipelined_matches_sequential_at_lr0(model_setup):
    """With lr=0 the one-step-stale pipeline is exactly the sequential loop."""
    model, _, feats, masks = model_setup
    tx = make_optimizer(TrainConfig(lr=0.0, grad_clip=5.0), 10)
    rng_np = np.random.default_rng(0)
    labels = jnp.asarray(rng_np.integers(4, V, size=(8, 5)), jnp.int32)
    state = create_train_state(model, tx, (feats, masks, labels), seed=1)

    cfg = RLConfig(enabled=True, num_rollouts=2, baseline="greedy")
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg)
    vids = [f"v{i}" for i in range(8)]
    batches = [(feats, masks, vids, None)] * 3

    _, pipelined = trainer.train_epoch(state, iter(batches), jax.random.key(9))

    sequential = []
    rng = jax.random.key(9)
    s = state
    for f, m, v, _ in batches:
        rng, srng = jax.random.split(rng)
        s, mt = trainer.train_step(s, f, m, v, srng)
        sequential.append(mt)
    assert len(pipelined) == len(sequential) == 3
    for mp, ms in zip(pipelined, sequential):
        assert mp["reward_mean"] == pytest.approx(ms["reward_mean"])
        assert float(mp["rl_loss"]) == pytest.approx(float(ms["rl_loss"]), rel=1e-5)


def test_scst_trainer_with_mesh_learns(model_setup):
    """Full sharded cycle (decode+update over the mesh) still learns."""
    model, state, feats, masks = model_setup
    mesh = make_mesh()
    cfg = RLConfig(enabled=True, num_rollouts=4, baseline="greedy")
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg, mesh=mesh)
    vids = [f"v{i}" for i in range(8)]
    state = replicate(mesh, state)
    f_s, m_s = shard_batch(mesh, (feats, masks))
    rng = jax.random.key(2)
    rewards = []
    for _ in range(15):
        rng, srng = jax.random.split(rng)
        state, m = trainer.train_step(state, f_s, m_s, vids, srng)
        rewards.append(m["reward_mean"])
    assert rewards[-1] > rewards[0] + 0.5, f"{rewards[0]:.2f}->{rewards[-1]:.2f}"


@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_mixed_reward_matches_both_oracles(native):
    """w_c*CIDErD + w_b*BLEU4*10 against BOTH oracles at once (config 4)."""
    from cst_captioning_tpu.metrics.bleu import Bleu
    from cst_captioning_tpu.metrics.cider import CiderD, CorpusDF

    rng = np.random.default_rng(4)
    vocab = make_vocab()
    vids = ["a", "b", "c"]
    gts = {
        v: [" ".join(rng.choice(WORDS, size=rng.integers(4, 9))) for _ in range(4)]
        for v in vids
    }
    refs = {v: [c.split() for c in caps] for v, caps in gts.items()}
    df = CorpusDF.from_refs(list(refs.values()))
    w_c, w_b = 0.8, 0.2
    rc = _reward_computer(vocab, gts, native, df=df, cider_weight=w_c,
                          bleu_weight=w_b)
    rows = np.stack(
        [
            np.asarray(
                (vocab.encode(list(rng.choice(WORDS, size=5))) + [EOS_ID] + [0] * 10)[:10],
                np.int32,
            )
            for _ in range(9)
        ]
    )
    got = rc(vids, rows)

    cider = CiderD(df=df)
    bleu = Bleu(4)
    hyps = [vocab.decode(r).split() for r in rows]
    o_gts = {str(i): refs[vids[i % 3]] for i in range(9)}
    o_res = {str(i): [hyps[i]] for i in range(9)}
    _, cider_scores = cider.compute_score(o_gts, o_res)
    for i in range(9):
        b4 = bleu.sentence_bleu(hyps[i], refs[vids[i % 3]])[3]
        want = w_c * cider_scores[i] + w_b * b4 * 10.0
        np.testing.assert_allclose(got[i], want, rtol=1e-5, atol=1e-7)


def test_native_oov_token_ids_match_python_path():
    """Ids >= len(vocab) (model vocab > dataset vocab) score as '<unk>' on
    both paths (ADVICE r1: native used to clip to the LAST vocab word)."""
    vocab = make_vocab()
    gts = {"v0": ["w0 w1 <unk>", "w0 w1 w2"]}
    rc_py = _reward_computer(vocab, gts, native=False)
    rc_nat = _reward_computer(vocab, gts, native=True)
    # row with an in-vocab prefix and a wildly out-of-range id
    row = np.asarray([[vocab.encode(["w0"])[0], vocab.encode(["w1"])[0],
                       len(vocab) + 123, EOS_ID, 0]], np.int32)
    r_py = rc_py(["v0"], row)
    r_nat = rc_nat(["v0"], row)
    np.testing.assert_allclose(r_nat, r_py, rtol=1e-6)
    assert r_py[0] > 0  # the '<unk>' gram genuinely matched a reference


@pytest.mark.parametrize("native", [False, True], ids=["python", "native"])
def test_reward_bleu_scale_knob(native):
    """rl.reward_bleu4_scale scales the BLEU term linearly on both paths
    (ADVICE r3 #4: the x10 convention is an unverified interpretation of the
    reference — the knob lets it be matched without code changes)."""
    vocab = make_vocab()
    gts = {"v0": ["w0 w1 w2 w3 w4", "w0 w1 w2 w5 w6"]}
    row = np.asarray(
        [vocab.encode("w0 w1 w2 w3 w6".split()) + [EOS_ID]], np.int32
    )
    r_cider = _reward_computer(
        vocab, gts, native, cider_weight=1.0, bleu_weight=0.0
    )(["v0"], row)[0]
    r_10 = _reward_computer(
        vocab, gts, native, cider_weight=1.0, bleu_weight=0.5, bleu_scale=10.0
    )(["v0"], row)[0]
    r_2 = _reward_computer(
        vocab, gts, native, cider_weight=1.0, bleu_weight=0.5, bleu_scale=2.0
    )(["v0"], row)[0]
    bleu_term_10 = r_10 - r_cider
    bleu_term_2 = r_2 - r_cider
    assert bleu_term_10 > 0
    np.testing.assert_allclose(bleu_term_2, bleu_term_10 / 5.0, rtol=1e-5)
    # scale folds out entirely at weight 0
    r_w0 = _reward_computer(
        vocab, gts, native, cider_weight=1.0, bleu_weight=0.0, bleu_scale=99.0
    )(["v0"], row)[0]
    np.testing.assert_allclose(r_w0, r_cider, rtol=1e-6)


def test_reward_threads_explicit_matches_default():
    """num_threads is a pure partitioning knob: scores are identical."""
    vocab = make_vocab()
    gts = {f"v{i}": [f"w{i % 9} w{(i + 1) % 9}"] for i in range(16)}
    rc1 = _reward_computer(vocab, gts, native=True, num_threads=1)
    rc4 = _reward_computer(vocab, gts, native=True, num_threads=4)
    assert rc1.num_threads == 1 and rc4.num_threads == 4
    rng = np.random.default_rng(3)
    # enough rows (>=64) to take the threaded path in the kernel
    rows = rng.integers(0, V, size=(96, 6)).astype(np.int32)
    vids = [f"v{i % 16}" for i in range(16)]
    np.testing.assert_array_equal(rc1(vids, rows), rc4(vids, rows))


def test_train_epoch_strict_flag_matches_train_step(model_setup):
    """pipelined=False is exactly the reference's on-policy loop: bit-equal
    params and metrics to calling train_step per batch with the same rng."""
    model, state, feats, masks = model_setup
    cfg = RLConfig(enabled=True, num_rollouts=2, baseline="greedy",
                   pipelined=False)
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg)
    vids = [f"v{i}" for i in range(8)]
    batches = [(feats, masks, vids, None)] * 3

    s_epoch, strict = trainer.train_epoch(
        state, iter(batches), jax.random.key(5), pipelined=cfg.pipelined
    )

    rng = jax.random.key(5)
    s_manual = state
    manual = []
    for f, m, v, _ in batches:
        rng, srng = jax.random.split(rng)
        s_manual, mt = trainer.train_step(s_manual, f, m, v, srng)
        manual.append(mt)
    assert len(strict) == len(manual) == 3
    for mp, ms in zip(strict, manual):
        assert mp["reward_mean"] == pytest.approx(ms["reward_mean"])
        assert float(mp["rl_loss"]) == float(ms["rl_loss"])
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        s_epoch.params, s_manual.params,
    )


def test_train_epoch_pipelined_matches_one_deep_schedule_at_lr(model_setup):
    """The update(i-2)->decode(i)->score(i-1) dispatch order is bit-identical
    to the 1-deep decode(i)->score(i-1)->update(i-1) pipeline at a REAL
    learning rate: the update that lands between two decodes is the same one,
    only its dispatch point moved off the host's critical path."""
    model, _, feats, masks = model_setup
    tx = make_optimizer(TrainConfig(lr=5e-2, grad_clip=5.0), 10)
    rng_np = np.random.default_rng(0)
    labels = jnp.asarray(rng_np.integers(4, V, size=(8, 5)), jnp.int32)
    state = create_train_state(model, tx, (feats, masks, labels), seed=1)

    cfg = RLConfig(enabled=True, num_rollouts=2, baseline="greedy")
    trainer = SCSTTrainer(model, TokenReward(target=7), cfg)
    vids = [f"v{i}" for i in range(8)]
    batches = [(feats, masks, vids, None)] * 4

    s_new, new = trainer.train_epoch(state, iter(batches), jax.random.key(9))

    # reference implementation: the round-3 1-deep pipelined loop
    rng = jax.random.key(9)
    s_old = state
    old = []
    pending = None
    for f, m, v, _ in batches:
        rng, srng = jax.random.split(rng)
        decoded = trainer.decode(s_old.params, f, m, srng)
        if pending is not None:
            s_old, mt = trainer._finish(s_old, *pending)
            old.append(mt)
        greedy, samples = decoded
        pending = (greedy, samples, f, m, v, np.ones((8,), np.float32))
    s_old, mt = trainer._finish(s_old, *pending)
    old.append(mt)

    assert len(new) == len(old) == 4
    for mp, ms in zip(new, old):
        assert mp["reward_mean"] == pytest.approx(ms["reward_mean"])
        assert float(mp["rl_loss"]) == float(ms["rl_loss"])
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        s_new.params, s_old.params,
    )


# ---- the pipeline primed across the epoch's end ------------------------------


class _Recording:
    """An ``SCSTTrainer`` whose decode, reward and update write down, in
    dispatch order, which batch each was called for: ``(what, epoch, i)``.
    Batches are told apart by their first video id (``e<epoch>b<i>.v0``) and
    by the feature array they carry."""

    def __init__(self, model, epochs=3, n=4, B=8, **cfg):
        self.log: list[tuple] = []
        self.tag_of: dict[int, tuple] = {}
        rl_cfg = RLConfig(enabled=True, num_rollouts=2, baseline="greedy",
                          **cfg)
        self.trainer = t = SCSTTrainer(model, self._reward, rl_cfg)
        self._decode, self._update = t.decode, t.update
        t.decode, t.update = self.decode, self.update
        self._target = TokenReward(target=7)
        self.B = B
        self.keys = [jax.random.key(100 + e) for e in range(epochs)]
        self.n = n

    def batches(self, model_setup, e, n=None):
        _, _, feats, masks = model_setup
        out = []
        for i in range(self.n if n is None else n):
            f = {"resnet": feats["resnet"] * (1.0 + 0.05 * (self.n * e + i))}
            self.tag_of[id(f["resnet"])] = (e, i)
            vids = [f"e{e}b{i}.v{k}" for k in range(self.B)]
            out.append((f, masks, vids, None))
        return out

    def decode(self, params, feats, masks, rng):
        self.log.append(("decode", *self.tag_of[id(feats["resnet"])]))
        return self._decode(params, feats, masks, rng)

    def update(self, state, feats, *rest):
        self.log.append(("update", *self.tag_of[id(feats["resnet"])]))
        return self._update(state, feats, *rest)

    def _reward(self, video_ids, rows):
        e, i = video_ids[0][1:].split(".")[0].split("b")
        if not self.log or self.log[-1] != ("score", int(e), int(i)):
            self.log.append(("score", int(e), int(i)))  # greedy + samples
        return self._target(video_ids, rows)

    def one_deep(self, state, epochs):
        """The reference: the 1-deep decode(i) -> score(i-1) -> update(i-1)
        loop over the epochs' batches end to end, each batch decoded with the
        key its own epoch's split chain gives it. Returns the state after
        every update."""
        t, states, pending = self.trainer, [], None
        t.decode, t.update = self._decode, self._update
        try:
            for e, batches in enumerate(epochs):
                rng = self.keys[e]
                for f, m, v, _ in batches:
                    rng, srng = jax.random.split(rng)
                    d = t.decode(state.params, f, m, srng)
                    if pending is not None:
                        state, _ = t._finish(state, *pending)
                        states.append(state)
                    pending = (*d, f, m, v, np.ones((self.B,), np.float32))
            state, _ = t._finish(state, *pending)
            states.append(state)
        finally:
            t.decode, t.update = self.decode, self.update
            del self.log[:]
        return states


def _same_params(a, b):
    jax.tree.map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x), np.asarray(y)),
        a.params, b.params,
    )


def _epoch_counts():
    return np.array([obs.counter(f"rl.epoch.{k}").snapshot()
                     for k in ("primed", "cold")])


def _slots(e, first, last):
    """update(i-2) -> decode(i) -> score(i-1) for batches first..last of
    epoch ``e``, the two before them already in flight."""
    out = []
    for i in range(first, last + 1):
        out += [("update", e, i - 2), ("decode", e, i), ("score", e, i - 1)]
    return out


def test_train_epoch_primed_across_the_epochs_end(model_setup):
    """Two epochs of four batches, the second opened by ``next_epoch`` inside
    the first one's drain: the dispatch order runs on over the cut, the state
    the first call returns holds exactly its four updates and four metrics,
    the second call begins with the primed pair, and the parameters after
    both are bit-identical to the 1-deep loop over all eight batches with
    each batch's own key."""
    model, state, _, _ = model_setup
    rec = _Recording(model)
    e0, e1 = rec.batches(model_setup, 0), rec.batches(model_setup, 1)
    ref = rec.one_deep(state, [e0, e1])
    counts0 = _epoch_counts()
    steps: list[int] = []
    opened = []

    def next_epoch():
        opened.append(iter(e1))
        return opened[0], rec.keys[1]

    sink: dict = {}
    s0, m0 = rec.trainer.train_epoch(
        state, iter(e0), rec.keys[0], on_step=lambda m: steps.append(0),
        seam_sink=sink, next_epoch=next_epoch,
    )
    assert rec.log == (
        [("decode", 0, 0), ("decode", 0, 1), ("score", 0, 0)]
        + _slots(0, 2, 3)
        # the fill of epoch 1 inside the drain of epoch 0
        + [("update", 0, 2), ("decode", 1, 0), ("score", 0, 3),
           ("update", 0, 3), ("decode", 1, 1), ("score", 1, 0)]
    )
    # (b) exactly this epoch's updates, none of the next epoch's
    assert len(m0) == 4 and steps == [0] * 4 and int(s0.step) == 4
    _same_params(s0, ref[3])
    # what the next call needs is the trainer's to keep; the sink is a stop's
    primed = rec.trainer.primed
    assert sink == {} and primed.batches is opened[0]
    tokens = rec.trainer.primed_seam()
    assert tokens["next_epoch"] is True and tokens["video_ids"] == e1[0][2]
    np.testing.assert_array_equal(tokens["samples"],
                                  np.asarray(primed.first[0][1]))

    del rec.log[:]
    sink1: dict = {}
    s1, m1 = rec.trainer.train_epoch(
        s0, primed.batches, primed.rng, on_step=lambda m: steps.append(1),
        seam_sink=sink1,
    )
    del primed
    assert rec.log == _slots(1, 2, 3) + [
        ("update", 1, 2), ("score", 1, 3), ("update", 1, 3)]
    assert len(m1) == 4 and steps == [0] * 4 + [1] * 4 and int(s1.step) == 8
    assert sink1 == {}      # the last epoch drains as ever
    # taken over, not copied: nothing keeps the pair's batches alive
    assert rec.trainer.primed is None and rec.trainer.primed_seam() is None
    _same_params(s1, ref[7])
    assert (_epoch_counts() - counts0).tolist() == [1, 1]


def _run_phase(rec, state, epochs, **kw):
    """What ``Trainer.train_rl`` does with ``train_epoch`` over a phase: the
    next epoch's iterator and key through ``next_epoch`` on every epoch but
    the last, and what the trainer kept of them (``primed``) as the next
    call's batches and key. Returns the state and the number of metrics
    after every call."""
    its = [iter(b) for b in epochs]
    ends = []
    for e in range(len(epochs)):
        nxt = None
        if e + 1 < len(epochs):
            nxt = lambda e=e: (its[e + 1], rec.keys[e + 1])  # noqa: E731
        primed = rec.trainer.primed
        batches, key = (its[e], rec.keys[e]) if primed is None else (
            primed.batches, primed.rng)
        del primed
        state, m = rec.trainer.train_epoch(
            state, batches, key, seam_sink={}, next_epoch=nxt, **kw,
        )
        ends.append((state, len(m)))
    return ends


@pytest.mark.parametrize("sizes", [(4, 4), (1, 3), (3, 1, 2), (2, 2, 2),
                                   (1, 1, 1)],
                         ids=lambda s: "-".join(map(str, s)))
def test_primed_phase_matches_one_deep_for_any_epoch_sizes(model_setup, sizes):
    """Whatever the epochs' lengths (an epoch of one batch hands over a
    decoded batch and nothing scored; one of two enters the next call with
    nothing left to fetch), every call returns its own epoch's updates and
    no more, and the phase is the 1-deep loop over all batches end to end."""
    model, state, _, _ = model_setup
    rec = _Recording(model)
    epochs = [rec.batches(model_setup, e, n) for e, n in enumerate(sizes)]
    ref = rec.one_deep(state, epochs)
    done = 0
    for (s, n_metrics), n in zip(_run_phase(rec, state, epochs), sizes,
                                 strict=True):
        done += n
        assert n_metrics == n and int(s.step) == done
        _same_params(s, ref[done - 1])
    # every batch decoded once, scored once and applied once, in order
    for what in ("decode", "score", "update"):
        assert [x[1:] for x in rec.log if x[0] == what] == [
            (e, i) for e, n in enumerate(sizes) for i in range(n)]


_DRAINS_AS_EVER = {
    # (train_epoch's further arguments, polls of should_stop before it says
    # stop (None: never), what the first call's sink holds)
    "last_epoch": (dict(next_epoch=None), None, set()),
    "no_next_epoch": (dict(next_epoch=lambda: None), None, set()),
    "strict": (dict(pipelined=False), None, set()),
    "no_sink": (dict(seam_sink=None), None, None),
    "stop_before_priming": ({}, 4, {"samples", "greedy", "video_ids",
                                    "next_epoch"}),
    "stop_while_priming": ({}, 5, {"samples", "greedy", "video_ids",
                                   "next_epoch"}),
}


@pytest.mark.parametrize("case", list(_DRAINS_AS_EVER))
def test_train_epoch_drains_as_ever_where_it_does_not_prime(model_setup, case):
    """The phase's last epoch, a phase with no epoch to follow, the strict
    loop, a caller with no sink, and a stop that arrives before or while
    priming: the call ends with its own four updates applied and nothing
    handed over, and counts as a cold start (``rl.epoch.cold``). A stop
    while priming leaves the next epoch's first batch's tokens in the sink
    (position: the next epoch's batch 0); a run resumed from the returned
    state with them is bit-identical to the primed one."""
    model, state, _, _ = model_setup
    kw, stop_after, holds = _DRAINS_AS_EVER[case]
    rec = _Recording(model, pipelined=kw.get("pipelined", True))
    e0, e1 = rec.batches(model_setup, 0), rec.batches(model_setup, 1)
    strict = kw.get("pipelined") is False
    if strict:
        ref, s = [], state
        for e, batches in enumerate((e0, e1)):
            rng = rec.keys[e]
            for f, m, v, _ in batches:
                rng, srng = jax.random.split(rng)
                s, _ = rec.trainer.train_step(s, f, m, v, srng)
                ref.append(s)
        del rec.log[:]
    else:
        ref = rec.one_deep(state, [e0, e1])
    polls = itertools.count()
    sink: dict = {}
    it1 = iter(e1)
    args = dict(seam_sink=sink, next_epoch=lambda: (it1, rec.keys[1]),
                should_stop=lambda: (stop_after is not None
                                     and next(polls) >= stop_after))
    args.update(kw)
    counts0 = _epoch_counts()
    s0, m0 = rec.trainer.train_epoch(state, iter(e0), rec.keys[0], **args)
    assert len(m0) == 4 and int(s0.step) == 4
    _same_params(s0, ref[3])
    assert (_epoch_counts() - counts0).tolist() == [0, 1]
    assert rec.trainer.primed is None       # nothing is kept
    if holds is not None:
        assert set(sink) == holds
    ahead = [x for x in rec.log if x[1] == 1]
    if stop_after is None:
        assert not ahead        # nothing of the next epoch was touched
        assert rec.log[-3:] == (
            [("decode", 0, 3), ("score", 0, 3), ("update", 0, 3)] if strict
            else [("update", 0, 2), ("score", 0, 3), ("update", 0, 3)])
        return
    # the stop: update(n-2) -> decode(0') as the seam, then this epoch's rest
    assert ahead == [("decode", 1, 0)]
    assert rec.log[-4:] == [("update", 0, 2), ("decode", 1, 0),
                            ("score", 0, 3), ("update", 0, 3)]
    assert sink["next_epoch"] is True and sink["video_ids"] == e1[0][2]
    del rec.log[:]
    s1, m1 = rec.trainer.train_epoch(
        s0, iter(e1), rec.keys[1], seam=dict(sink, epoch=1, batch_index=0),
        seam_sink={},
    )
    assert ("decode", 1, 0) not in rec.log and ("decode", 1, 1) in rec.log
    assert len(m1) == 4
    _same_params(s1, ref[7])
