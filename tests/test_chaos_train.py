"""Chaos-driven trainer tests: the resilience acceptance criteria.

Every scenario here is a seeded :class:`FaultPlan` driving the injection
points compiled into the trainer/ckpt/rl hot paths (resilience/chaos.py):

- SIGTERM mid-epoch -> mid-epoch save -> resume -> bit-identical to the
  uninterrupted run (params AND per-step losses);
- NaN-poisoned batch under ``skip_batch`` -> epoch completes with the batch
  excluded (step counter excludes it, params stay finite);
- ``rollback`` -> last-good checkpoint restored, data order re-salted, run
  completes; ``abort`` -> TrainingDiverged;
- truncated ``state.msgpack`` -> manifest checksum detects it, the previous
  checkpoint is restored, a ``ckpt_corrupt`` event is logged;
- transient reward-scorer failures -> retried with logged ``reward_retry``.
"""

import dataclasses
import json
import os

import jax
import numpy as np
import pytest

from cst_captioning_tpu.config.config import (
    DataConfig,
    EvalConfig,
    ExperimentConfig,
    MeshConfig,
    ModelConfig,
    RLConfig,
    TrainConfig,
)
from cst_captioning_tpu.data import CaptionDataset, make_synthetic_dataset
from cst_captioning_tpu.resilience import (
    Fault,
    FaultPlan,
    PeerLost,
    Preempted,
    TrainingDiverged,
)
from cst_captioning_tpu.train.trainer import Trainer


@pytest.fixture(scope="module")
def synth_dir(tmp_path_factory):
    out = tmp_path_factory.mktemp("chaossynth")
    return make_synthetic_dataset(
        str(out),
        num_videos=12,
        num_topics=3,
        vocab_words=20,
        modalities={"resnet": 16},
        max_frames=4,
        seed=5,
    )


@pytest.fixture(scope="module")
def datasets(synth_dir):
    train = CaptionDataset(
        synth_dir["info_json"], {"resnet": synth_dir["resnet"]}, "train", 4
    )
    val = CaptionDataset(
        synth_dir["info_json"], {"resnet": synth_dir["resnet"]}, "val", 4
    )
    return train, val


def make_cfg(ckpt_dir: str, vocab_size: int, *, pipelined: bool = False,
             batch_size: int = 8, seq_per_vid: int = 2, num_devices: int = 0,
             rl_epochs: int = 2, **train_kw) -> ExperimentConfig:
    train_kw.setdefault("eval_every_epochs", 100)
    train_kw.setdefault("epochs", 2)
    return ExperimentConfig(
        name="chaos",
        model=ModelConfig(
            vocab_size=vocab_size,
            modalities=(("resnet", 16),),
            d_embed=16,
            d_hidden=16,
            d_att=8,
            encoder="temporal_attention",
            dropout=0.0,
            max_len=8,
            max_frames=4,
            dtype="float32",
        ),
        data=DataConfig(batch_size=batch_size, seq_per_vid=seq_per_vid),
        train=TrainConfig(
            lr=5e-3, grad_clip=5.0, ckpt_dir=ckpt_dir, seed=0,
            log_every_steps=1, **train_kw,
        ),
        rl=RLConfig(
            enabled=True, num_rollouts=2, lr=1e-3, epochs=rl_epochs,
            baseline="greedy", pipelined=pipelined,
        ),
        eval=EvalConfig(beam_size=1, max_len=8),
        mesh=MeshConfig(num_devices=num_devices),
    )


def events_of(log_path, kind):
    return [
        e for e in (json.loads(l) for l in open(log_path))
        if e["event"] == kind
    ]


def params_equal(a, b):
    for x, y in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    ):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# 12 videos x seq_per_vid=2 = 24 rows / batch_size 8 = 3 XE batches per epoch
STEPS_PER_EPOCH = 3


def test_sigterm_mid_epoch_resume_is_bit_identical(datasets, tmp_path_factory):
    """ISSUE acceptance #1: kill mid-epoch via chaos plan, resume, per-step
    losses and final params match the uninterrupted run bit-for-bit."""
    train_ds, _ = datasets
    d1 = str(tmp_path_factory.mktemp("straight"))
    d2 = str(tmp_path_factory.mktemp("preempted"))

    cfg1 = make_cfg(d1, len(train_ds.vocab))
    tr_straight = Trainer(cfg1, train_ds, None, log_path=d1 + "/ev.jsonl",
                          use_mesh=False)
    tr_straight.train_xe()

    # SIGTERM lands after step 5 = batch 2 of epoch 2 (0-based visit 4)
    cfg2 = make_cfg(d2, len(train_ds.vocab))
    tr_kill = Trainer(cfg2, train_ds, None, log_path=d2 + "/ev.jsonl",
                      use_mesh=False)
    plan = FaultPlan([Fault("xe.step", "preempt", at=STEPS_PER_EPOCH + 1)])
    with plan.activate():
        with pytest.raises(Preempted):
            tr_kill.train_xe()
    assert plan.fired and plan.fired[0]["kind"] == "preempt"
    assert events_of(d2 + "/ev.jsonl", "preempt")[0]["batch_index"] == 2
    # the mid-epoch checkpoint recorded the exact position
    step_dirs = [n for n in os.listdir(d2) if n.startswith("step_")]
    assert len(step_dirs) == 1
    infos = json.load(open(os.path.join(d2, step_dirs[0], "infos.json")))
    assert infos["phase"] == "xe" and infos["batch_index"] == 2
    assert infos["xe_epochs"] == 1  # one COMPLETED epoch

    # rerun the same command with resume: replays the epoch remainder
    cfg_resume = dataclasses.replace(
        cfg2, train=dataclasses.replace(cfg2.train, resume="auto")
    )
    tr_res = Trainer(cfg_resume, train_ds, None, log_path=d2 + "/ev2.jsonl",
                     use_mesh=False)
    assert tr_res._resume_batch == 2
    tr_res.train_xe()

    assert tr_res.xe_epochs == tr_straight.xe_epochs == 2
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)

    # per-step losses: pre-kill steps 1-5 + resumed step 6 == straight 1-6
    straight = {
        e["step"]: e["loss"] for e in events_of(d1 + "/ev.jsonl", "xe_step")
    }
    chaos_run = {
        e["step"]: e["loss"] for e in events_of(d2 + "/ev.jsonl", "xe_step")
    }
    chaos_run.update({
        e["step"]: e["loss"] for e in events_of(d2 + "/ev2.jsonl", "xe_step")
    })
    assert chaos_run == straight  # bit-for-bit (json round-trips repr floats)


def test_nan_batch_skipped_epoch_completes(datasets, tmp_path_factory):
    """ISSUE acceptance #2: a NaN-poisoned batch under skip_batch completes
    the epoch with the batch excluded."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("nanskip"))
    cfg = make_cfg(d, len(train_ds.vocab), epochs=1)
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    plan = FaultPlan([Fault("xe.batch", "nan", at=1)])
    with plan.activate():
        tr.train_xe()
    assert tr.xe_epochs == 1
    # the poisoned batch is EXCLUDED: the device-side guard suppressed its
    # update, so the step counter advanced for 2 of the 3 batches only
    assert int(tr.state.step) == STEPS_PER_EPOCH - 1
    for leaf in jax.tree_util.tree_leaves(tr.state.params):
        assert np.isfinite(np.asarray(leaf)).all()
    div = events_of(d + "/ev.jsonl", "divergence")
    assert len(div) == 1
    assert div[0]["kind"] == "nonfinite" and div[0]["action"] == "skip_batch"
    # the epoch summary excludes the NaN loss scalar too
    (ep,) = events_of(d + "/ev.jsonl", "xe_epoch")
    assert np.isfinite(ep["loss"])


def test_nan_batch_abort_policy_raises(datasets, tmp_path_factory):
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("nanabort"))
    cfg = make_cfg(d, len(train_ds.vocab), epochs=1, on_divergence="abort")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    with FaultPlan([Fault("xe.batch", "nan", at=1)]).activate():
        with pytest.raises(TrainingDiverged):
            tr.train_xe()


def test_nan_batch_rollback_restores_and_resalts(datasets, tmp_path_factory):
    """Divergence in epoch 2 under rollback: restore the epoch-1 checkpoint,
    re-randomize the order (salt), and still finish the full budget."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("nanroll"))
    cfg = make_cfg(d, len(train_ds.vocab), on_divergence="rollback")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    # poison one batch of epoch 2 (visits 3..5); the replayed (salted) epoch
    # uses later visit indices, so the poison does not re-fire
    with FaultPlan([Fault("xe.batch", "nan", at=STEPS_PER_EPOCH + 1)]).activate():
        tr.train_xe()
    assert tr.xe_epochs == 2 and tr.epoch == 2
    assert tr.batcher.salt == 1
    (rb,) = events_of(d + "/ev.jsonl", "rollback")
    assert rb["restored_epoch"] == 1 and rb["salt"] == 1
    for leaf in jax.tree_util.tree_leaves(tr.state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_truncated_checkpoint_resume_falls_back(datasets, tmp_path_factory):
    """ISSUE acceptance #3: a truncated state.msgpack is caught by the
    manifest checksum; resume logs ckpt_corrupt and restores the previous
    checkpoint."""
    train_ds, val_ds = datasets
    d = str(tmp_path_factory.mktemp("trunc"))
    cfg = make_cfg(d, len(train_ds.vocab), epochs=1, eval_every_epochs=1)
    Trainer(cfg, train_ds, val_ds, use_mesh=False).train_xe()  # latest + best
    sp = os.path.join(d, "latest", "state.msgpack")
    with open(sp, "r+b") as f:
        f.truncate(os.path.getsize(sp) // 2)

    cfg_resume = dataclasses.replace(
        cfg, train=dataclasses.replace(cfg.train, resume="auto")
    )
    tr = Trainer(cfg_resume, train_ds, None, log_path=d + "/ev.jsonl",
                 use_mesh=False)
    assert tr.epoch == 1  # restored (from 'best') despite the corrupt latest
    (ev,) = events_of(d + "/ev.jsonl", "ckpt_corrupt")
    assert ev["name"] == "latest"
    assert ev["error"] == "CorruptCheckpointError"
    assert "state.msgpack" in ev["detail"]
    assert events_of(d + "/ev.jsonl", "resume")


def test_step_interval_checkpoints_rotate(datasets, tmp_path_factory):
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("interval"))
    cfg = make_cfg(
        d, len(train_ds.vocab), ckpt_every_steps=2, keep_ckpts=2,
    )
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    tr.train_xe()  # 6 steps -> saves at 2, 4, 6; rotation keeps the last 2
    assert [s for s, _ in tr.ckpt.step_checkpoints()] == [4, 6]
    saves = events_of(d + "/ev.jsonl", "ckpt_step")
    assert [e["step"] for e in saves] == [2, 4, 6]
    # batch_index recorded relative to the epoch (3 steps per epoch)
    assert [e["batch_index"] for e in saves] == [2, 1, 3]


def test_rl_preemption_strict_resume_is_bit_identical(datasets, tmp_path_factory):
    """RL twin of the SIGTERM parity test, in strict (pipelined=False) mode:
    preempt mid-RL-epoch, resume, final params match the uninterrupted run
    bit-for-bit (batch order, sampling rng chain, and optimizer moments all
    continue mid-epoch)."""
    train_ds, _ = datasets
    d1 = str(tmp_path_factory.mktemp("rlstraight"))
    d2 = str(tmp_path_factory.mktemp("rlpreempt"))

    def run(ckpt_dir, resume=""):
        cfg = make_cfg(ckpt_dir, len(train_ds.vocab), epochs=1, resume=resume)
        tr = Trainer(cfg, train_ds, None, log_path=ckpt_dir + "/ev.jsonl",
                     use_mesh=False)
        tr.train_xe()
        tr.train_rl()
        return tr

    tr_straight = run(d1)

    # 12 videos / batch 8 = 2 RL batches/epoch; preempt in epoch 2 batch 1
    # (0-based visit 2 of rl.step)
    with FaultPlan([Fault("rl.step", "preempt", at=2)]).activate():
        with pytest.raises(Preempted):
            run(d2)
    saves = events_of(d2 + "/ev.jsonl", "ckpt_step")
    assert saves and saves[-1]["phase"] == "rl"
    assert saves[-1]["batch_index"] == 1

    tr_res = run(d2, resume="auto")
    assert tr_res.rl_epochs == tr_straight.rl_epochs == 2
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)


def test_transient_reward_failures_are_retried(datasets, tmp_path_factory):
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("rewardretry"))
    cfg = make_cfg(d, len(train_ds.vocab), epochs=1)
    cfg = dataclasses.replace(cfg, rl=dataclasses.replace(cfg.rl, epochs=1))
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    tr.train_xe()
    with FaultPlan([Fault("reward.call", "io_error", at=0, times=1)]).activate():
        tr.train_rl()
    assert tr.rl_epochs == 1
    retries = events_of(d + "/ev.jsonl", "reward_retry")
    assert len(retries) == 1 and retries[0]["error"] == "TransientIOError"


# ---- elastic resilience: seam parity, partial preemption, degraded mesh -----


def test_rl_pipelined_preempt_seam_resume_is_bit_identical(datasets,
                                                           tmp_path_factory):
    """Drain-aware save order (ISSUE 6 satellite #1): preempting the
    PIPELINED RL loop mid-epoch persists the decoded-but-unscored seam batch
    next to the checkpoint; the resumed run replays those tokens, so per-step
    rewards/losses and final params match the uninterrupted pipelined run
    bit-for-bit (previously the seam batch was re-decoded against params one
    update fresher)."""
    train_ds, _ = datasets
    d1 = str(tmp_path_factory.mktemp("seamstraight"))
    d2 = str(tmp_path_factory.mktemp("seampreempt"))

    def run(ckpt_dir, resume=""):
        # batch_size 2 -> 5 RL batches/epoch (10 train videos): deep
        # enough that the stop
        # lands mid-pipeline (2 in flight) instead of at the epoch boundary
        cfg = make_cfg(ckpt_dir, len(train_ds.vocab), pipelined=True,
                       batch_size=2, seq_per_vid=1, epochs=1, resume=resume)
        tr = Trainer(cfg, train_ds, None, log_path=ckpt_dir + "/ev.jsonl",
                     use_mesh=False)
        tr.train_xe()
        tr.train_rl()
        return tr

    tr_straight = run(d1)

    # 5 rl.step visits per epoch; visit 6 = the second update emitted in
    # epoch 2 -> the loop stops at the NEXT iteration top, mid-pipeline
    with FaultPlan([Fault("rl.step", "preempt", at=6)]).activate():
        with pytest.raises(Preempted):
            run(d2)
    saves = events_of(d2 + "/ev.jsonl", "ckpt_step")
    assert saves and saves[-1]["phase"] == "rl"
    assert 0 < saves[-1]["batch_index"] < 5  # genuinely mid-epoch
    assert saves[-1]["seam"] is True
    step_dirs = [n for n in os.listdir(d2) if n.startswith("step_")]
    assert any(
        os.path.exists(os.path.join(d2, s, "seam.npz")) for s in step_dirs
    )

    tr_res = run(d2, resume="auto")
    assert events_of(d2 + "/ev.jsonl", "seam_loaded")
    assert tr_res.rl_epochs == tr_straight.rl_epochs == 2
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)

    # the per-step reward/loss streams agree bit-for-bit across the seam
    def rl_steps(*paths):
        out = {}
        for p in paths:
            if os.path.exists(p):
                for e in events_of(p, "rl_step"):
                    out[e["step"]] = (e["reward"], e["rl_loss"])
        return out

    straight = rl_steps(d1 + "/ev.jsonl")
    chaosrun = rl_steps(d2 + "/ev.jsonl", d2 + "/ev2.jsonl")
    # the resumed process logs into ev.jsonl again (same path): both runs'
    # events are in d2/ev.jsonl; dedup by step keeps the comparison exact
    assert chaosrun == straight


# ---- the RL pipeline primed across the epoch's end ---------------------------

# 10 train videos / batch 2 = 5 RL batches an epoch; rl.step visit v is the
# (v % 5)-th update of RL epoch v // 5
RL_BATCHES = 5


def _run_primed(train_ds, ckpt_dir, resume="", **kw):
    cfg = make_cfg(ckpt_dir, len(train_ds.vocab), pipelined=True,
                   batch_size=2, seq_per_vid=1, epochs=1, rl_epochs=3,
                   resume=resume, **kw)
    tr = Trainer(cfg, train_ds, None, log_path=ckpt_dir + "/ev.jsonl",
                 use_mesh=False)
    tr.train_xe()
    tr.train_rl()
    return tr


def _rl_steps(path):
    return {e["step"]: (e["reward"], e["rl_loss"])
            for e in events_of(path, "rl_step")}


@pytest.fixture(scope="module")
def primed_straight(datasets, tmp_path_factory):
    """Three pipelined RL epochs, uninterrupted: the second and the third
    begin with the pair the epoch before decoded inside its drain."""
    d = str(tmp_path_factory.mktemp("primedstraight"))
    return _run_primed(datasets[0], d), d


# what stops the run in the second RL epoch, and where the loop notices it.
# (The second: the RL phase counts its steps from 0, so a step save of the
# first RL epoch would tie with the XE phase's last save. A slot polls the
# stop, then dispatches the pending update: a stop raised in update(i)'s
# step is noticed at the poll after it.)
_BOUNDARY_STOPS = {
    # update(n-3), dispatched in batch n-1's slot: noticed at the first
    # priming poll -> update(n-2), decode(0') as the seam, drain
    "stop_before_priming": Fault("rl.step", "preempt", at=2 * RL_BATCHES - 3),
    # update(n-2), dispatched in batch 0''s slot: noticed at the second
    # poll, decode(1') never dispatched
    "stop_while_priming": Fault("rl.step", "preempt", at=2 * RL_BATCHES - 2),
    # the scoring of batch 0' (two reward calls a batch, greedy baseline),
    # the call's last act: noticed by the Trainer when it has returned with
    # the primed pair
    "stop_after_priming": Fault("reward.call", "preempt",
                                at=4 * RL_BATCHES),
    # the process dies on the next epoch's first step: what is left is the
    # epoch-end checkpoint
    "killed_after_epoch_end_checkpoint": Fault("rl.step", "kill",
                                               at=2 * RL_BATCHES),
}


@pytest.mark.parametrize("case", list(_BOUNDARY_STOPS))
def test_rl_primed_epoch_end_resume_is_bit_identical(datasets, primed_straight,
                                                     tmp_path_factory, case):
    """A pipelined RL run stopped at an epoch's end (before, while or after
    the next epoch's first two batches are decoded inside its drain), or
    killed and resumed from the epoch-end checkpoint, continues bit for bit
    as the uninterrupted run: the state holds exactly the epoch's updates,
    and the next epoch's first batch's tokens ride beside it as the seam at
    (epoch + 1, batch 0), because that batch was decoded one update before
    the saved state."""
    from cst_captioning_tpu.resilience.chaos import SimulatedKill

    train_ds, _ = datasets
    tr_straight, d1 = primed_straight
    d2 = str(tmp_path_factory.mktemp("primed_" + case))
    fault = _BOUNDARY_STOPS[case]
    with FaultPlan([fault]).activate():
        with pytest.raises(SimulatedKill if fault.kind == "kill"
                           else Preempted):
            _run_primed(train_ds, d2)
    if fault.kind == "kill":
        name = "latest"
        assert not events_of(d2 + "/ev.jsonl", "ckpt_step")
    else:
        save = events_of(d2 + "/ev.jsonl", "ckpt_step")[-1]
        # every batch of the epoch is applied; the seam is the next epoch's
        assert save["phase"] == "rl" and save["seam"] is True
        assert save["batch_index"] == RL_BATCHES
        name = f"step_{save['step']:08d}"
    with np.load(os.path.join(d2, name, "seam.npz")) as z:
        # global epoch 2 is the second RL epoch (one XE epoch before them)
        assert (int(z["epoch"]), int(z["batch_index"])) == (3, 0)
    infos = json.load(open(os.path.join(d2, name, "infos.json")))
    assert infos["global_step"] == 2 * RL_BATCHES
    assert "extra_files" not in infos

    tr_res = _run_primed(train_ds, d2, resume="auto")
    loaded = events_of(d2 + "/ev.jsonl", "seam_loaded")
    assert loaded and (loaded[-1]["epoch"], loaded[-1]["batch_index"]) == (3, 0)
    assert not events_of(d2 + "/ev.jsonl", "seam_discarded")
    assert tr_res.rl_epochs == tr_straight.rl_epochs == 3
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)
    assert _rl_steps(d2 + "/ev.jsonl") == _rl_steps(d1 + "/ev.jsonl")


def _one_deep_train_epoch():
    """A plain reference for ``SCSTTrainer.train_epoch`` in a primed phase:
    decode(i) -> score(i-1) -> update(i-1), one batch deep and nothing else,
    run on over every epoch's end. The batch decoded and not yet applied
    (the next epoch's first) and that epoch's key after its split are
    carried to the next call, which skips the batch in its own iterator."""
    carried: dict = {}

    def train_epoch(self, state, batches, rng, on_step=None, next_epoch=None,
                    **kw):
        out = []
        pending = carried.pop("pending", None)
        if pending is not None:
            rng = carried.pop("rng")
            assert next(batches)[2] == pending[4]

        def decode(batch, srng):
            f, m, v, valid = batch
            d = self.decode(state.params, f, m, srng)
            return (*d, f, m, v, self._valid_np(valid, len(v)))

        def finish():
            nonlocal state
            state, m = self._finish(state, *pending)
            out.append(m)
            on_step(m)

        for batch in batches:
            rng, srng = jax.random.split(rng)
            decoded = decode(batch, srng)
            if pending is not None:
                finish()
            pending = decoded
        ahead = next_epoch() if next_epoch is not None else None
        if ahead is not None:
            rng, srng = jax.random.split(ahead[1])
            decoded = decode(next(ahead[0]), srng)
            ahead[0].close()
            finish()
            carried.update(pending=decoded, rng=rng)
        else:
            finish()
        return state, out

    return train_epoch


def test_rl_primed_phase_is_one_loop_over_the_phase(datasets, primed_straight,
                                                    tmp_path_factory,
                                                    monkeypatch):
    """``train_rl`` over three pipelined epochs IS one 1-deep pipelined loop
    over the phase's batches end to end (every batch decoded one update
    stale, an epoch's first included): parameters and per-step rewards and
    losses are those of a plain 1-deep reference driven through the same
    Trainer. A phase cut into calls of one epoch each begins every epoch
    with fresh parameters instead, and lands elsewhere."""
    from cst_captioning_tpu.rl.scst import SCSTTrainer

    train_ds, _ = datasets
    tr_straight, d1 = primed_straight
    d2 = str(tmp_path_factory.mktemp("primedref"))
    with monkeypatch.context() as mp:
        mp.setattr(SCSTTrainer, "train_epoch", _one_deep_train_epoch())
        tr_ref = _run_primed(train_ds, d2)
    assert int(tr_ref.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_ref.state.params)
    assert _rl_steps(d2 + "/ev.jsonl") == _rl_steps(d1 + "/ev.jsonl")

    d3 = str(tmp_path_factory.mktemp("primedcut"))
    cfg = make_cfg(d3, len(train_ds.vocab), pipelined=True, batch_size=2,
                   seq_per_vid=1, epochs=1, rl_epochs=3)
    tr_cut = Trainer(cfg, train_ds, None, log_path=d3 + "/ev.jsonl",
                     use_mesh=False)
    tr_cut.train_xe()
    for _ in range(3):
        tr_cut.train_rl(epochs=1)
    assert _rl_steps(d3 + "/ev.jsonl") != _rl_steps(d1 + "/ev.jsonl")


def test_rl_rollback_drops_the_primed_pair(datasets, tmp_path_factory):
    """A divergence found in an epoch's drain, when the next epoch's first
    two batches are already decoded: the rollback drops the pair with the
    staged batches (the replayed epoch is re-salted, so neither is its), the
    replay begins with an empty pipeline, and the phase still runs out."""
    import threading

    from cst_captioning_tpu import obs

    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("primedroll"))

    def counts():
        return np.array([obs.counter(f"rl.epoch.{k}").snapshot()
                         for k in ("primed", "cold")])

    c0 = counts()
    # rl.batch visit 7: the third batch of the second RL epoch
    with FaultPlan([Fault("rl.batch", "nan", at=RL_BATCHES + 2)]).activate():
        tr = _run_primed(train_ds, d, on_divergence="rollback")
    (rb,) = events_of(d + "/ev.jsonl", "rollback")
    assert rb["restored_epoch"] == 2 and rb["salt"] == 1
    assert tr.rl_epochs == 3 and tr.batcher.salt == 1
    assert tr._pending_seam is None
    assert not [t for t in threading.enumerate() if t.name == "prefetch"]
    # RL epochs 1, 2, 2 again, 3: the replay of 2 is the one more cold start
    assert (counts() - c0).tolist() == [2, 2]
    for leaf in jax.tree_util.tree_leaves(tr.state.params):
        assert np.isfinite(np.asarray(leaf)).all()


def test_partial_preempt_xe_strict_drains_and_raises(datasets,
                                                     tmp_path_factory):
    """partial_preempt during XE under elastic='strict': drain -> durable
    save -> PeerLost (today's abort-and-full-restart semantics)."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("xepartial"))
    cfg = make_cfg(d, len(train_ds.vocab), epochs=2, health=True,
                   health_sim_hosts=2)
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl",
                 use_mesh=False)
    try:
        plan = FaultPlan(
            [Fault("xe.step", "partial_preempt", at=STEPS_PER_EPOCH, host=1)]
        )
        with plan.activate():
            with pytest.raises(PeerLost) as ei:
                tr.train_xe()
        assert ei.value.hosts == [1]
        (drain,) = events_of(d + "/ev.jsonl", "peer_loss_drain")
        assert drain["phase"] == "xe" and drain["lost"] == [1]
        assert events_of(d + "/ev.jsonl", "peer_lost")
        # the drain saved a restorable mid-epoch checkpoint
        assert [n for n in os.listdir(d) if n.startswith("step_")]
    finally:
        tr.close()


def test_partial_preempt_strict_full_mesh_restart_is_bit_exact(
        datasets, tmp_path_factory):
    """ISSUE 6 acceptance (strict half): losing 1 of 2 simulated hosts
    mid-RL-epoch drains + saves; the strict fallback aborts, and a FULL-mesh
    restart resumes bit-exactly (params match the uninterrupted 2-device
    run)."""
    train_ds, _ = datasets
    d1 = str(tmp_path_factory.mktemp("strictstraight"))
    d2 = str(tmp_path_factory.mktemp("strictpartial"))

    def run(ckpt_dir, resume="", health=True, health_dir=""):
        cfg = make_cfg(ckpt_dir, len(train_ds.vocab), epochs=1,
                       num_devices=2, resume=resume, health=health,
                       health_sim_hosts=2, health_dir=health_dir)
        tr = Trainer(cfg, train_ds, None, log_path=ckpt_dir + "/ev.jsonl")
        try:
            tr.train_xe()
            tr.train_rl()
        finally:
            tr.close()
        return tr

    tr_straight = run(d1)

    # 2 RL batches/epoch -> visit 2 is epoch 2's first step; the strict loop
    # stops at the next batch boundary, drains, saves, raises PeerLost
    with FaultPlan(
        [Fault("rl.step", "partial_preempt", at=2, host=1)]
    ).activate():
        with pytest.raises(PeerLost):
            run(d2)
    (drain,) = events_of(d2 + "/ev.jsonl", "peer_loss_drain")
    assert drain["phase"] == "rl" and drain["batch_index"] == 1

    # full-mesh restart (a fresh health incarnation: the old tombstone
    # belongs to the dead cluster generation)
    tr_res = run(d2, resume="auto",
                 health_dir=str(tmp_path_factory.mktemp("hb2")))
    assert tr_res.rl_epochs == tr_straight.rl_epochs == 2
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)


def test_partial_preempt_degraded_mesh_continuation(datasets,
                                                    tmp_path_factory):
    """ISSUE 6 acceptance (degraded half): killing 1 of 2 simulated hosts
    mid-RL-epoch triggers drain -> durable save -> survivor rendezvous ->
    shrunk 1-device mesh with optimizer state resharded from the drained
    checkpoint -> training continues in the SAME process: reward trajectory
    stays finite, every epoch completes, no epoch is skipped."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("degraded"))
    cfg = make_cfg(d, len(train_ds.vocab), pipelined=True, batch_size=2,
                   seq_per_vid=1, epochs=1, num_devices=2, health=True,
                   health_sim_hosts=2, elastic="degraded")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
    try:
        tr.train_xe()
        assert tr.mesh is not None and tr.mesh.devices.size == 2
        # 5 RL batches/epoch (10 train videos); visit 6 = the second
        # update emitted in epoch 2 -> peer loss lands mid-epoch,
        # mid-pipeline
        plan = FaultPlan(
            [Fault("rl.step", "partial_preempt", at=6, host=1)]
        )
        with plan.activate():
            tr.train_rl()  # survives: drain + degraded continuation inside
        assert [f["kind"] for f in plan.fired] == ["partial_preempt"]

        # the run finished its full budget on the shrunk mesh
        assert tr.rl_epochs == 2
        assert tr.mesh is not None and tr.mesh.devices.size == 1
        assert tr.health.survivors() == [0]

        (drain,) = events_of(d + "/ev.jsonl", "peer_loss_drain")
        assert drain["phase"] == "rl" and 0 < drain["batch_index"] < 5
        (deg,) = events_of(d + "/ev.jsonl", "degraded_mesh")
        assert deg["lost"] == [1] and deg["survivors"] == [0]
        assert deg["devices"] == 1 and deg["resumed_phase"] == "rl"

        # trajectory continues: every RL epoch reports, rewards stay finite,
        # the step clock never rewinds or skips
        rl_eps = events_of(d + "/ev.jsonl", "rl_epoch")
        assert [e["epoch"] for e in rl_eps] == [2, 3]
        assert all(np.isfinite(e["reward"]) for e in rl_eps)
        steps = [e["step"] for e in events_of(d + "/ev.jsonl", "rl_step")]
        assert sorted(set(steps)) == list(range(1, 11))  # 2 epochs x 5 steps
        rewards = [
            e["reward"] for e in events_of(d + "/ev.jsonl", "rl_step")
        ]
        losses = [
            e["rl_loss"] for e in events_of(d + "/ev.jsonl", "rl_step")
        ]
        assert np.isfinite(rewards).all() and np.isfinite(losses).all()
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            assert np.isfinite(np.asarray(leaf)).all()
        # the drained seam was replayed, not re-decoded
        assert events_of(d + "/ev.jsonl", "seam_loaded")
    finally:
        tr.close()


def test_partial_preempt_then_rejoin_regrows_full_mesh(datasets,
                                                       tmp_path_factory):
    """ISSUE 17 acceptance (grow-back half): after the degraded-mesh
    continuation, the lost host announces recovery, is validated, and is
    re-admitted at the next batch boundary — drain -> durable save -> full
    rendezvous -> rebuilt 2-device mesh with the rejoiner's state
    replicated from the survivors' drained checkpoint (never its stale
    one). The run finishes its full budget on the FULL mesh with a
    contiguous step clock, and the post-regrow step program is
    bit-identical to a never-degraded trainer resumed from the same
    checkpoint (one-epoch params + opt_state comparison)."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("regrown"))
    cfg = make_cfg(d, len(train_ds.vocab), pipelined=True, batch_size=2,
                   seq_per_vid=1, epochs=1, num_devices=2, health=True,
                   health_sim_hosts=2, elastic="degraded")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
    try:
        tr.train_xe()
        # visit 0 of rl.step = the very first RL step, so the pipelined
        # drain lands mid-epoch (seam) and most of the budget runs AFTER
        # the regrow; visit 0 of health.rejoin = the first poll after the
        # degraded continuation announces host 1's recovery
        plan = FaultPlan([
            Fault("rl.step", "partial_preempt", at=0, host=1),
            Fault("health.rejoin", "host_rejoin", at=0, host=1),
        ])
        with plan.activate():
            tr.train_rl()  # shrinks, then regrows, inside
        assert [f["kind"] for f in plan.fired] == [
            "partial_preempt", "host_rejoin",
        ]

        # the run finished its full budget back on the FULL mesh
        assert tr.rl_epochs == 2
        assert tr.mesh is not None and tr.mesh.devices.size == 2
        assert tr.health.survivors() == [0, 1]
        assert tr.health.generation == 2  # shrink bumped to 1, regrow to 2

        (deg,) = events_of(d + "/ev.jsonl", "degraded_mesh")
        assert deg["lost"] == [1]
        (rd,) = events_of(d + "/ev.jsonl", "regrow_drain")
        assert rd["phase"] == "rl" and rd["rejoiner"] == 1
        (rg,) = events_of(d + "/ev.jsonl", "mesh_regrow")
        assert rg["rejoiner"] == 1 and rg["devices"] == 2
        assert rg["hosts"] == [0, 1] and rg["generation"] == 2
        assert not events_of(d + "/ev.jsonl", "regrow_refused")

        # trajectory: every epoch reports, the step clock never rewinds or
        # skips through shrink OR regrow, dynamics stay finite
        rl_eps = events_of(d + "/ev.jsonl", "rl_epoch")
        assert [e["epoch"] for e in rl_eps] == [2, 3]
        steps = [e["step"] for e in events_of(d + "/ev.jsonl", "rl_step")]
        assert sorted(set(steps)) == list(range(1, 11))
        rewards = [
            e["reward"] for e in events_of(d + "/ev.jsonl", "rl_step")
        ]
        assert np.isfinite(rewards).all()
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            assert np.isfinite(np.asarray(leaf)).all()

        # program-identity pin: a fresh never-degraded trainer resumed from
        # the same checkpoint runs one more epoch bit-identically to the
        # regrown in-memory trainer (params AND opt_state)
        cfg2 = make_cfg(d, len(train_ds.vocab), pipelined=True, batch_size=2,
                        seq_per_vid=1, epochs=1, num_devices=2, resume="auto")
        tr2 = Trainer(cfg2, train_ds, None, log_path=d + "/ev2.jsonl")
        assert tr2.epoch == tr.epoch
        assert int(tr2.state.step) == int(tr.state.step)
        params_equal(tr.state.params, tr2.state.params)
        tr.train_rl(epochs=1)
        tr2.train_rl(epochs=1)
        params_equal(tr.state.params, tr2.state.params)
        params_equal(tr.state.opt_state, tr2.state.opt_state)
    finally:
        tr.close()


def test_flaky_rejoin_leaves_degraded_run_unharmed(datasets,
                                                   tmp_path_factory):
    """A rejoiner that announces recovery and then dies mid-rendezvous
    (``host_rejoin_flaky``) must not damage the degraded run: the
    survivors time out the regrow rendezvous, refuse the admission, and
    continue on the shrunk mesh with params bit-identical to a run where
    no rejoin was ever attempted."""
    train_ds, _ = datasets

    def run(d, extra_faults):
        cfg = make_cfg(d, len(train_ds.vocab), pipelined=True, batch_size=2,
                       seq_per_vid=1, epochs=1, num_devices=2, health=True,
                       health_sim_hosts=2, elastic="degraded",
                       peer_timeout_s=0.2)  # fast rendezvous timeout
        tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
        try:
            tr.train_xe()
            plan = FaultPlan(
                [Fault("rl.step", "partial_preempt", at=0, host=1)]
                + extra_faults
            )
            with plan.activate():
                tr.train_rl()
            assert tr.rl_epochs == 2
            return tr, jax.device_get(tr.state.params)
        finally:
            tr.close()

    d_plain = str(tmp_path_factory.mktemp("norejoins"))
    d_flaky = str(tmp_path_factory.mktemp("flakyrejoin"))
    _, params_plain = run(d_plain, [])
    tr_b, params_flaky = run(d_flaky, [
        Fault("health.rejoin", "host_rejoin_flaky", at=0, host=1),
    ])

    # the flaky run drained for the admission, timed out, refused it, and
    # stayed degraded for its whole remaining budget
    assert events_of(d_flaky + "/ev.jsonl", "regrow_drain")
    (ref,) = events_of(d_flaky + "/ev.jsonl", "regrow_refused")
    assert ref["rejoiner"] == 1
    assert not events_of(d_flaky + "/ev.jsonl", "mesh_regrow")
    assert tr_b.mesh is not None and tr_b.mesh.devices.size == 1
    assert tr_b.health.survivors() == [0]
    steps = [e["step"] for e in events_of(d_flaky + "/ev.jsonl", "rl_step")]
    assert sorted(set(steps)) == list(range(1, 11))
    # the failed admission left the trajectory untouched
    params_equal(params_plain, params_flaky)


def test_enospc_during_training_rotation_recovers(datasets, tmp_path_factory):
    """ENOSPC mid-run: the step-interval save reclaims the oldest step_*
    generation, retries, and training never notices."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("enospc"))
    cfg = make_cfg(d, len(train_ds.vocab), ckpt_every_steps=2, keep_ckpts=2)
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl", use_mesh=False)
    # saves land at steps 2, 4, 6 -> ckpt.save visits 0, 1, 2; the disk
    # "fills up" at the third save and recovers by deleting the oldest gen
    with FaultPlan(
        [Fault("ckpt.save", "enospc_rotation", at=2, times=1)]
    ).activate():
        tr.train_xe()
    assert tr.xe_epochs == 2
    (ev,) = events_of(d + "/ev.jsonl", "ckpt_enospc")
    assert ev["freed"] == ["step_00000002"]
    assert [s for s, _ in tr.ckpt.step_checkpoints()] == [4, 6]


# ---- decoupled actor/learner topology ---------------------------------------


@pytest.mark.slow
def test_decoupled_preempt_ring_seam_resume_is_bit_identical(
        datasets, tmp_path_factory, monkeypatch):
    """Decoupled-topology twin of the pipelined seam test: preempting the
    actor/learner loop mid-epoch persists the in-flight rollout RING next
    to the checkpoint; the resume replays those exact tokens. With shared
    roles (use_mesh=False) the default depth-2/bound-1 ring IS the sync
    1-deep pipeline, so the whole chain — straight decoupled, preempted +
    resumed decoupled, straight pipelined sync — lands on bit-identical
    params, PER EPOCH-SIZED CALL: the ring fills and drains inside every
    epoch, as the sync loop does when it is asked for one epoch a call. The
    sync loop given the phase of two primes the second epoch across the
    boundary (its first batch decoded one update stale): that run is pinned
    to the plain 1-deep loop over the whole phase instead."""
    from cst_captioning_tpu.rl.scst import SCSTTrainer

    train_ds, _ = datasets
    d0 = str(tmp_path_factory.mktemp("decsync"))
    d1 = str(tmp_path_factory.mktemp("decstraight"))
    d2 = str(tmp_path_factory.mktemp("decpreempt"))

    def run(ckpt_dir, resume="", topology="decoupled", epoch_calls=False):
        cfg = make_cfg(ckpt_dir, len(train_ds.vocab), pipelined=True,
                       batch_size=2, seq_per_vid=1, epochs=1, resume=resume,
                       rl_topology=topology)
        tr = Trainer(cfg, train_ds, None, log_path=ckpt_dir + "/ev.jsonl",
                     use_mesh=False)
        tr.train_xe()
        if epoch_calls:
            for _ in range(cfg.rl.epochs):
                tr.train_rl(epochs=1)
        else:
            tr.train_rl()
        return tr

    tr_sync = run(d0, topology="sync", epoch_calls=True)
    tr_straight = run(d1)
    # shared roles + depth 2 + bound 1 replays the sync pipelined schedule
    params_equal(tr_sync.state.params, tr_straight.state.params)
    # the two-epoch sync phase in one call: one loop over both epochs
    tr_phase = run(str(tmp_path_factory.mktemp("decphase")), topology="sync")
    with monkeypatch.context() as mp:
        mp.setattr(SCSTTrainer, "train_epoch", _one_deep_train_epoch())
        tr_ref = run(str(tmp_path_factory.mktemp("decref")), topology="sync")
    params_equal(tr_phase.state.params, tr_ref.state.params)

    # 5 rl.step visits per epoch; visit 6 = the second update of epoch 2
    # -> the stop lands with a decoded-but-unscored ring entry in flight
    with FaultPlan([Fault("rl.step", "preempt", at=6)]).activate():
        with pytest.raises(Preempted):
            run(d2)
    saves = events_of(d2 + "/ev.jsonl", "ckpt_step")
    assert saves and saves[-1]["phase"] == "rl"
    assert 0 < saves[-1]["batch_index"] < 5
    assert saves[-1]["seam"] is True
    step_dirs = [n for n in os.listdir(d2) if n.startswith("step_")]
    assert any(
        os.path.exists(os.path.join(d2, s, "seam.npz")) for s in step_dirs
    )

    tr_res = run(d2, resume="auto")
    assert events_of(d2 + "/ev.jsonl", "seam_loaded")
    assert tr_res.rl_epochs == tr_straight.rl_epochs == 2
    assert int(tr_res.state.step) == int(tr_straight.state.step)
    params_equal(tr_straight.state.params, tr_res.state.params)


@pytest.mark.slow
def test_decoupled_actor_preempt_degrades_to_survivors(datasets,
                                                       tmp_path_factory):
    """Seeded actor_preempt recovery: losing one actor device mid-epoch
    sheds it, survivors keep decoding, the orphaned in-flight rollouts are
    recounted, and every epoch completes with finite dynamics."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("actorshed"))
    # 4 devices -> 2 actors / 2 learners; one preempt leaves 1 survivor
    cfg = make_cfg(d, len(train_ds.vocab), num_devices=4,
                   rl_topology="decoupled")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
    try:
        tr.train_xe()
        with FaultPlan(
            [Fault("rl.actor.step", "actor_preempt", at=1)]
        ).activate():
            tr.train_rl()
        assert tr.rl_epochs == 2
        (deg,) = events_of(d + "/ev.jsonl", "rl_actor_degraded")
        assert deg["survivors"] == 1
        assert not events_of(d + "/ev.jsonl", "rl_actor_fallback_sync")
        rewards = [
            e["reward"] for e in events_of(d + "/ev.jsonl", "rl_step")
        ]
        assert rewards and np.isfinite(rewards).all()
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            assert np.isfinite(np.asarray(leaf)).all()
    finally:
        tr.close()


@pytest.mark.slow
def test_decoupled_actor_rejoin_regrows_fleet(datasets, tmp_path_factory):
    """ISSUE 17 actor-fleet arc: an ``actor_preempt`` sheds one actor, a
    later ``host_rejoin`` re-admits it — the rollout ring re-binds to the
    grown submesh, orphaned in-flight rollouts are recounted in order, and
    every epoch completes with finite dynamics on the restored fleet."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("actorregrow"))
    # 4 devices -> 2 actors / 2 learners; preempt actor 0, then rejoin it
    cfg = make_cfg(d, len(train_ds.vocab), num_devices=4,
                   rl_topology="decoupled")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
    try:
        tr.train_xe()
        plan = FaultPlan([
            Fault("rl.actor.step", "actor_preempt", at=1),
            Fault("rl.actor.step", "host_rejoin", at=3),
        ])
        with plan.activate():
            tr.train_rl()
        assert [f["kind"] for f in plan.fired] == [
            "actor_preempt", "host_rejoin",
        ]
        assert tr.rl_epochs == 2
        (deg,) = events_of(d + "/ev.jsonl", "rl_actor_degraded")
        assert deg["survivors"] == 1
        regrown = events_of(d + "/ev.jsonl", "rl_actor_regrown")
        assert regrown and regrown[0]["actors"] == 2  # the initial fleet
        assert not events_of(d + "/ev.jsonl", "rl_actor_fallback_sync")
        rewards = [
            e["reward"] for e in events_of(d + "/ev.jsonl", "rl_step")
        ]
        assert rewards and np.isfinite(rewards).all()
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            assert np.isfinite(np.asarray(leaf)).all()
    finally:
        tr.close()


@pytest.mark.slow
def test_decoupled_zero_actor_falls_back_to_sync(datasets, tmp_path_factory):
    """When the last actor is preempted the decoupled loop degrades all the
    way to the sync schedule on the learner submesh and training still
    completes — no crash, no lost batches."""
    train_ds, _ = datasets
    d = str(tmp_path_factory.mktemp("actorzero"))
    # 2 devices -> 1 actor / 1 learner; the single preempt exhausts actors
    cfg = make_cfg(d, len(train_ds.vocab), num_devices=2,
                   rl_topology="decoupled")
    tr = Trainer(cfg, train_ds, None, log_path=d + "/ev.jsonl")
    try:
        tr.train_xe()
        with FaultPlan(
            [Fault("rl.actor.step", "actor_preempt", at=1)]
        ).activate():
            tr.train_rl()
        assert tr.rl_epochs == 2
        assert events_of(d + "/ev.jsonl", "rl_actor_fallback_sync")
        # 2 RL batches/epoch x 2 epochs: every batch still produced a step
        steps = {e["step"] for e in events_of(d + "/ev.jsonl", "rl_step")}
        assert len(steps) == 4
        for leaf in jax.tree_util.tree_leaves(tr.state.params):
            assert np.isfinite(np.asarray(leaf)).all()
    finally:
        tr.close()
