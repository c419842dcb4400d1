"""The per-epoch sampling key: one fold-in program for every epoch.

``train/state.py``'s ``device_fold_in`` takes the folded integer (the epoch,
the rollback salt) as a traced ``uint32`` argument, uploaded by an explicit
``device_put``: the keys are the eager ``jax.random.fold_in``'s bit for bit,
a new epoch compiles nothing, and nothing transfers implicitly. The
Trainer-level test holds ``train_rl`` to the same: epochs past the first
compile no fold-in program, and a resumed phase stays on the uninterrupted
run's stream.

``no_sanitize``: the tests scope ``jax.transfer_guard`` themselves (and on
every run, not only under ``--sanitize``); the eager reference spelling they
compare with IS the implicit transfer.
"""

import contextlib
import dataclasses
import json

import numpy as np
import pytest

import jax
from jax import monitoring

# the tiny captioner and its corpus are test_sanitize's (importing the
# fixture registers it here too)
from test_sanitize import _cfg as sanitize_cfg
from test_sanitize import sanitize_datasets  # noqa: F401

from cst_captioning_tpu.train.state import device_fold_in, device_key
from cst_captioning_tpu.train.trainer import Trainer

pytestmark = pytest.mark.no_sanitize

# the benchmark's warmed counts and the epochs around them (23, 24, 48, 57),
# and the ends of the signed and unsigned 32-bit ranges
FOLDED = [0, 1, 2, 23, 24, 48, 57, 2**31 - 1, 2**31, 2**32 - 1]


def _bits(key) -> np.ndarray:
    return np.asarray(jax.device_get(jax.random.key_data(key)))


@contextlib.contextmanager
def fold_in_programs(tag=lambda: None):
    """``(tag(), fun_name)`` of every program lowered or compiled while the
    block runs whose name holds ``fold_in``. (A ``fold_in`` inside another
    program, the decode's per-device key, is traced and is no program.)"""
    seen = []

    def listener(event, _secs, fun_name="", **_kw):
        if event.endswith(("jaxpr_to_mlir_module_duration",
                           "backend_compile_duration")) \
                and "fold_in" in fun_name:
            seen.append((tag(), fun_name))

    monitoring.register_event_duration_secs_listener(listener)
    try:
        yield seen
    finally:
        monitoring.unregister_event_duration_listener(listener)


@pytest.mark.parametrize("n", FOLDED)
def test_fold_in_equals_the_eager_key_bit_for_bit(n):
    base = device_key(7)
    np.testing.assert_array_equal(
        _bits(device_fold_in(base, n)), _bits(jax.random.fold_in(base, n))
    )


@pytest.mark.parametrize("salt", [1, 3, 2**32 - 1])
@pytest.mark.parametrize("epoch", [0, 24, 2**31])
def test_salt_then_epoch_equals_the_eager_key_bit_for_bit(salt, epoch):
    """``Trainer._rl_epoch`` after a rollback: the salt, then the epoch."""
    base = device_key(1)
    eager = jax.random.fold_in(jax.random.fold_in(base, salt), epoch)
    np.testing.assert_array_equal(
        _bits(device_fold_in(device_fold_in(base, salt), epoch)), _bits(eager)
    )


@pytest.mark.parametrize("n", [-1, 2**32])
def test_fold_in_refuses_what_uint32_cannot_hold(n):
    with pytest.raises(OverflowError):
        device_fold_in(device_key(7), n)


def test_ten_epochs_and_a_salt_compile_one_program():
    """Fails on the static spelling: there every epoch was a program."""
    base = device_key(11)
    device_fold_in(base, 0)             # the one compile, if none came before
    with fold_in_programs() as seen:
        keys = [device_fold_in(base, epoch) for epoch in range(100, 110)]
        # a salted base is a committed key where device_key's is not: the
        # same program still
        salted = device_fold_in(base, 5)
        keys += [device_fold_in(salted, epoch) for epoch in range(100, 110)]
    assert seen == []
    assert len({_bits(k).tobytes() for k in keys}) == 20


def test_single_device_key_folds_under_transfer_guard():
    base = device_key(3)
    with jax.transfer_guard("disallow"):
        salted = device_fold_in(base, 2)
        key = device_fold_in(salted, 9)
        jax.block_until_ready(key)
    assert key.sharding == base.sharding
    np.testing.assert_array_equal(
        _bits(key),
        _bits(jax.random.fold_in(jax.random.fold_in(base, 2), 9)),
    )


# ---- the Trainer's RL phase -------------------------------------------------

@pytest.fixture
def train_ds(sanitize_datasets):  # noqa: F811
    return sanitize_datasets[0]


def _cfg(ckpt_dir: str, vocab_size: int, batch_size: int = 4,
         rl_epochs: int = 4, resume: str = ""):
    cfg = sanitize_cfg(ckpt_dir, vocab_size)
    return dataclasses.replace(
        cfg,
        data=dataclasses.replace(cfg.data, batch_size=batch_size),
        train=dataclasses.replace(cfg.train, epochs=1, resume=resume),
        rl=dataclasses.replace(cfg.rl, epochs=rl_epochs),
    )


def _rl_rewards(log_path: str) -> list[float]:
    events = [json.loads(line) for line in open(log_path)]
    return [e["reward"] for e in events if e["event"] == "rl_epoch"]


def test_mesh_rl_epochs_run_under_transfer_guard(train_ds, tmp_path_factory):
    """The RL half of test_sanitize's mesh test with the guard on in every
    run: the epoch reaches the device by its explicit ``device_put``, and
    the key folded on one device is replicated onto the mesh as before."""
    ckpt_dir = str(tmp_path_factory.mktemp("epoch_keys_mesh"))
    log_path = ckpt_dir + "/events.jsonl"
    cfg = _cfg(ckpt_dir, len(train_ds.vocab), batch_size=8, rl_epochs=2)
    tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=True)
    tr.train_xe()
    with jax.transfer_guard("disallow"):
        tr.train_rl()
    rewards = _rl_rewards(log_path)
    assert len(rewards) == 2 and all(r == r for r in rewards)


def test_rl_epochs_compile_no_key_program_and_resume_on_the_stream(
    train_ds, tmp_path_factory
):
    """Four RL epochs: those after the first compile no program whose name
    holds ``fold_in`` (the static spelling compiled one in each), and a phase
    interrupted after its second epoch and resumed draws the uninterrupted
    run's rewards: the stream is still keyed by the global epoch."""
    vocab = len(train_ds.vocab)
    trainers = []

    def run(ckpt_dir, resume="", rl_run_epochs=None):
        log_path = ckpt_dir + "/events.jsonl"
        cfg = _cfg(ckpt_dir, vocab, resume=resume)
        tr = Trainer(cfg, train_ds, None, log_path=log_path, use_mesh=False)
        trainers.append(tr)
        tr.train_xe()
        tr.train_rl(rl_run_epochs)
        return _rl_rewards(log_path)

    # tagged with the RL epochs done when the program came: epochs 3 and 4
    # begin with two and three done
    with fold_in_programs(lambda: trainers[-1].rl_epochs) as seen:
        straight = run(str(tmp_path_factory.mktemp("keys_straight")))
    assert len(straight) == 4
    assert [s for s in seen if s[0] >= 2] == []

    d = str(tmp_path_factory.mktemp("keys_resumed"))
    assert run(d, rl_run_epochs=2) == straight[:2]
    # the resumed process appends to the interrupted one's log
    assert run(d, resume="auto") == straight
