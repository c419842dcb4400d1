"""Runtime sanitizer gate: the zero-implicit-transfer hot-path claim.

graftlint GL001/GL013 prove *lexically* that the XE/RL step loops never
smuggle a host↔device transfer; these tests pin the same claim *at
runtime*. Setup (model init, optimizer build, eager constant staging) runs
UNGUARDED — exactly like production, where setup transfers are amortized —
and then the epoch hot loop runs inside ``jax.transfer_guard("disallow")``
+ ``jax.debug_nans``: any batch fed to a jitted step without an explicit
``device_put``, any eager scalar promotion inside the loop, and any NaN
update blows the test up.

``scripts/sanitize.sh`` drives this file (plus the blanket-guarded
``tests/test_data.py`` prefetch staging tests) with ``pytest --sanitize``;
without the flag the guard is a no-op and the tests double as plain
integration smoke, keeping the code path warm in tier-1.

The module is marked ``no_sanitize`` because the ``hot_guard`` fixture
scopes the guard itself: blanket-guarding the whole test would veto the
eager model init that setup legitimately performs.
"""

import contextlib
import json

import pytest

import jax

from cst_captioning_tpu.config.config import (
    DataConfig,
    EvalConfig,
    ExperimentConfig,
    ModelConfig,
    RLConfig,
    TrainConfig,
)
from cst_captioning_tpu.data import CaptionDataset, make_synthetic_dataset
from cst_captioning_tpu.train.trainer import Trainer

pytestmark = pytest.mark.no_sanitize


@pytest.fixture
def hot_guard(request):
    """Context-manager factory: the sanitizer clamp when --sanitize is on,
    a no-op otherwise."""
    if request.config.getoption("--sanitize"):
        @contextlib.contextmanager
        def guard():
            with jax.transfer_guard("disallow"), jax.debug_nans(True):
                yield

        return guard
    return contextlib.nullcontext


@pytest.fixture(scope="module")
def sanitize_datasets(tmp_path_factory):
    out = tmp_path_factory.mktemp("sanitize_synth")
    synth = make_synthetic_dataset(
        str(out), num_videos=8, num_topics=2, vocab_words=18,
        modalities={"resnet": 12}, max_frames=3, seed=7,
    )
    train = CaptionDataset(
        synth["info_json"], {"resnet": synth["resnet"]}, "train", 3
    )
    val = CaptionDataset(
        synth["info_json"], {"resnet": synth["resnet"]}, "val", 3
    )
    return train, val


def _cfg(ckpt_dir: str, vocab_size: int) -> ExperimentConfig:
    return ExperimentConfig(
        name="sanitize",
        model=ModelConfig(
            vocab_size=vocab_size, modalities=(("resnet", 12),),
            d_embed=12, d_hidden=12, d_att=8,
            encoder="temporal_attention", dropout=0.0,
            max_len=8, max_frames=3, dtype="float32",
        ),
        data=DataConfig(batch_size=4, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, epochs=2, grad_clip=5.0, ckpt_dir=ckpt_dir,
            eval_every_epochs=0, seed=0,
        ),
        rl=RLConfig(enabled=True, num_rollouts=2, lr=1e-3, epochs=1),
        eval=EvalConfig(beam_size=1, max_len=8),
    )


def test_xe_hot_loop_runs_clean_under_transfer_guard(
    sanitize_datasets, tmp_path_factory, hot_guard
):
    """Two full XE epochs (prefetch → sharded placement → jitted step →
    deferred readback) with zero implicit transfers and zero NaNs."""
    train_ds, _ = sanitize_datasets
    ckpt_dir = str(tmp_path_factory.mktemp("sanitize_xe"))
    log_path = ckpt_dir + "/events.jsonl"
    cfg = _cfg(ckpt_dir, len(train_ds.vocab))
    tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=False)
    with hot_guard():
        tr.train_xe()
    events = [json.loads(l) for l in open(log_path)]
    losses = [e["loss"] for e in events if e["event"] == "xe_epoch"]
    assert len(losses) == cfg.train.epochs
    assert all(l == l for l in losses), "non-finite XE loss"


def test_rl_hot_loop_runs_clean_under_transfer_guard(
    sanitize_datasets, tmp_path_factory, hot_guard
):
    """One SCST epoch (fused rollout decode → host reward → advantage
    upload → jitted update) under the same clamp: the decode→reward seam
    may read back EXPLICITLY, but nothing may transfer implicitly."""
    train_ds, _ = sanitize_datasets
    ckpt_dir = str(tmp_path_factory.mktemp("sanitize_rl"))
    log_path = ckpt_dir + "/events.jsonl"
    cfg = _cfg(ckpt_dir, len(train_ds.vocab))
    tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=False)
    tr.train_xe()  # unguarded warm start: RL resumes from XE params
    with hot_guard():
        tr.train_rl()
    events = [json.loads(l) for l in open(log_path)]
    rewards = [e["reward"] for e in events if e["event"] == "rl_epoch"]
    assert len(rewards) == cfg.rl.epochs
    assert all(r == r for r in rewards), "non-finite RL reward"


def test_mesh_hot_loops_run_clean_under_transfer_guard(
    sanitize_datasets, tmp_path_factory, hot_guard
):
    """The 8-fake-device mesh path: sharded batch placement, replicated
    epoch keys, and the sharded advantage upload must all be EXPLICIT
    placements — a single-device key or advantage would be re-scattered
    device-to-device on every dispatch (the regression this test pins)."""
    import dataclasses

    train_ds, _ = sanitize_datasets
    ckpt_dir = str(tmp_path_factory.mktemp("sanitize_mesh"))
    log_path = ckpt_dir + "/events.jsonl"
    cfg = _cfg(ckpt_dir, len(train_ds.vocab))
    cfg = dataclasses.replace(
        cfg, data=dataclasses.replace(cfg.data, batch_size=8)
    )
    tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=True)
    with hot_guard():
        tr.train_xe()
        tr.train_rl()
    events = [json.loads(l) for l in open(log_path)]
    losses = [e["loss"] for e in events if e["event"] == "xe_epoch"]
    rewards = [e["reward"] for e in events if e["event"] == "rl_epoch"]
    assert len(losses) == cfg.train.epochs and len(rewards) == cfg.rl.epochs
    assert all(x == x for x in losses + rewards)


@pytest.mark.parametrize("use_mesh", [False, True], ids=["one_device", "mesh"])
def test_primed_rl_epochs_run_clean_under_transfer_guard(
    sanitize_datasets, tmp_path_factory, hot_guard, use_mesh
):
    """Three pipelined RL epochs, the second and third primed inside the
    drain of the epoch before: the next epoch's key is folded and replicated
    there, its first two batches are decoded there, and the state's copy to
    the host is begun before the read-back asks for it: all of it explicit."""
    import dataclasses

    from cst_captioning_tpu import obs

    train_ds, _ = sanitize_datasets
    ckpt_dir = str(tmp_path_factory.mktemp("sanitize_primed"))
    log_path = ckpt_dir + "/events.jsonl"
    cfg = _cfg(ckpt_dir, len(train_ds.vocab))
    cfg = dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, batch_size=8 if use_mesh else 4),
        rl=dataclasses.replace(cfg.rl, epochs=3, pipelined=True),
    )
    tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=use_mesh)
    tr.train_xe()
    primed0 = obs.counter("rl.epoch.primed").snapshot()
    with hot_guard():
        tr.train_rl()
    assert obs.counter("rl.epoch.primed").snapshot() - primed0 == 2
    events = [json.loads(l) for l in open(log_path)]
    rewards = [e["reward"] for e in events if e["event"] == "rl_epoch"]
    assert len(rewards) == 3 and all(r == r for r in rewards)
