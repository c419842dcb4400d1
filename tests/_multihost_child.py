"""Child process for tests/test_multihost.py — NOT a pytest module.

Two modes (argv[7], default ``train``):

- ``train``: one member of a 2-process jax.distributed cluster (4 fake CPU
  devices each = 8 global), trains XE + RL through the Trainer with
  host-sharded data feeding, evaluates, and (process 0 only) dumps parity
  artifacts to json.
- ``partial_kill``: the elastic-path partial-kill harness — a REAL
  2-process cluster with per-process trainers (no cross-process
  computations, which the CPU backend cannot run) sharing one heartbeat
  dir. Process 1 (the victim) hard-dies mid-epoch via a seeded chaos kill;
  process 0 (the survivor) sleeps through the death window on a chaos
  ``slow`` fault, so its HealthMonitor declares the peer lost from
  heartbeat staleness BEFORE the next step — the survivor then drains
  (peer-loss save) and raises PeerLost (strict elastic). Each process
  reports its outcome to ``<out_json>.proc<pid>`` and hard-exits
  (``os._exit``) like a really-preempted host would.
"""

import json
import os
import sys


def _report(out_json: str, pid: int, payload: dict) -> None:
    with open(f"{out_json}.proc{pid}", "w") as f:
        json.dump(payload, f)


def partial_kill(pid: int, data_dir: str, out_json: str, tmp: str) -> None:
    import glob

    from cst_captioning_tpu.config.config import (
        DataConfig, ExperimentConfig, ModelConfig, TrainConfig,
    )
    from cst_captioning_tpu.data import CaptionDataset
    from cst_captioning_tpu.resilience.chaos import Fault, FaultPlan
    from cst_captioning_tpu.resilience.health import PeerLost
    from cst_captioning_tpu.train.trainer import Trainer

    ckpt_dir = os.path.join(tmp, f"pk_ckpt{pid}")
    ds = CaptionDataset(
        os.path.join(data_dir, "info.json"),
        {"resnet": os.path.join(data_dir, "resnet.h5")}, "train", 4,
    )
    cfg = ExperimentConfig(
        name="pk",
        model=ModelConfig(
            vocab_size=len(ds.vocab), modalities=(("resnet", 12),),
            d_embed=16, d_hidden=16, d_att=8,
            encoder="temporal_attention", dropout=0.0,
            max_len=8, max_frames=4, dtype="float32",
        ),
        data=DataConfig(batch_size=4, seq_per_vid=2),
        train=TrainConfig(
            lr=5e-3, epochs=2, ckpt_dir=ckpt_dir, eval_every_epochs=100,
            seed=0, health=True,
            health_dir=os.path.join(tmp, "pk_health"),  # SHARED heartbeats
            health_interval_s=0.1, peer_timeout_s=0.5, health_misses=2,
            elastic="strict",
        ),
    )
    # per-process trainer: NO shared mesh, so nothing here runs a
    # cross-process computation — the elastic signal under test is the
    # file-based heartbeat/watchdog/drain machinery, on real processes
    tr = Trainer(cfg, ds, None, use_mesh=False)
    if pid == 1:
        plan = FaultPlan([Fault("xe.step", "kill", at=2)])
    else:
        # sleep through the victim's death window: heartbeat staleness
        # (0.5s timeout, 2 misses, 0.1s polls) resolves well inside 2.5s,
        # so the boundary poll right after the sleep sees the loss
        plan = FaultPlan([Fault("xe.step", "slow", at=2, delay=2.5)])
    outcome: dict = {"initialized": True, "pid": pid}
    try:
        with plan.activate():
            tr.train_xe()
        outcome["finished"] = True
    except PeerLost as e:
        outcome["peer_lost"] = sorted(e.hosts)
        outcome["drained_ckpts"] = sorted(
            os.path.basename(p)
            for p in glob.glob(os.path.join(ckpt_dir, "step_*"))
        )
    except BaseException as e:  # SimulatedKill on the victim
        outcome["died"] = type(e).__name__
    _report(out_json, pid, outcome)
    ds.close()
    # hard exit, like the preempted host this models: no distributed
    # teardown handshaking with a cluster that just lost a member
    os._exit(0)


def main() -> None:
    pid = int(sys.argv[1])
    nproc = int(sys.argv[2])
    port = sys.argv[3]
    data_dir = sys.argv[4]
    out_json = sys.argv[5]
    tmp = sys.argv[6]
    mode = sys.argv[7] if len(sys.argv) > 7 else "train"

    # read when jax is imported / the CPU client starts, so set them first
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax

    from cst_captioning_tpu.train import multihost

    try:
        multihost.initialize(f"127.0.0.1:{port}", nproc, pid)
    except Exception as e:
        if mode == "partial_kill":
            _report(out_json, pid, {"initialized": False, "error": repr(e)})
            os._exit(0)
        raise
    assert jax.process_count() == nproc
    assert len(jax.devices()) == 4 * nproc

    if mode == "partial_kill":
        partial_kill(pid, data_dir, out_json, tmp)
        return

    import numpy as np  # noqa: F401 - kept for the train path's imports

    from tests.test_multihost import build_cfg, run_training  # noqa: F401

    result = run_training(
        data_dir, ckpt_dir=os.path.join(tmp, f"ckpt{pid}")
    )
    if pid == 0:
        with open(out_json, "w") as f:
            json.dump(result, f)


if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    main()
