"""Experiment driver: XE phase, CST/RL phase, validation, checkpointing.

The orchestration layer of the reference's ``train.py`` (SURVEY.md §3.1-3.2,
§3.5): epoch loop -> jitted steps -> per-epoch greedy validation scored by
CIDEr-D -> best/latest checkpoints -> optional resume -> XE->RL handoff.

Device placement: with a multi-device mesh the step is the shard_map-parallel
variant and batches are placed sharded; single device uses the plain jitted
step. Host batch prep overlaps device compute via the prefetch thread.

Resilience (resilience/ package): both phase loops run under a SIGTERM
preemption handler (mid-epoch save recording the exact batch index, so a
resumed run replays the *remainder* of the epoch — the epoch-keyed shuffle
makes that bit-deterministic; the pipelined RL drain additionally persists
the seam batch's tokens so resume is bit-identical in both pipeline modes),
a divergence sentinel with a configurable policy (``train.on_divergence``),
optional ``train.ckpt_every_steps`` mid-epoch ``step_*`` checkpoints with
keep-last-K rotation, and chaos injection points
(``xe.step``/``xe.batch``/``rl.step``/``rl.batch``) so the fault paths are
testable.

Elastic multi-host resilience (``train.health``, README "Elastic
training"): a heartbeat monitor + peer-loss watchdog
(resilience/health.py) lets the loops detect a lost host, drain + save,
and then either abort for a bit-exact full-mesh restart
(``train.elastic='strict'``) or rendezvous the survivors, rebuild a shrunk
data mesh, reshard optimizer state from the drained checkpoint, and keep
training (``'degraded'``).
"""

from __future__ import annotations

import io
import json
import os

import jax
import numpy as np
from jax.sharding import Mesh

from cst_captioning_tpu import obs
from cst_captioning_tpu.obs import anomaly as _anomaly
from cst_captioning_tpu.obs import flops as _flops
from cst_captioning_tpu.obs import recorder as flight
from cst_captioning_tpu.ckpt import CheckpointManager, load_params
from cst_captioning_tpu.ckpt.checkpoint import host_copy, host_copy_begin
from cst_captioning_tpu.config.config import EvalConfig, ExperimentConfig
from cst_captioning_tpu.data.batcher import Batcher, EpochKey
from cst_captioning_tpu.data.dataset import CaptionDataset
from cst_captioning_tpu.data.prefetch import PrefetchFeed, StagingRing
from cst_captioning_tpu.eval.evaluator import Evaluator
from cst_captioning_tpu.metrics.cider import CorpusDF
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.parallel import (
    CommConfig,
    make_sp_xe_step,
    sp_batch_shardings,
    sp_model,
)
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.resilience import health as health_mod
from cst_captioning_tpu.resilience.adaptive import AdaptiveThresholds
from cst_captioning_tpu.resilience.health import PeerLost
from cst_captioning_tpu.resilience.preempt import Preempted, PreemptionHandler
from cst_captioning_tpu.resilience.sentinel import (
    DivergenceSentinel,
    RollbackRequested,
    TrainingDiverged,
)
from cst_captioning_tpu.rl import AsyncSCSTTrainer, RewardComputer, SCSTTrainer
from cst_captioning_tpu.train import multihost
from cst_captioning_tpu.train.mesh import batch_sharding, make_mesh, replicate
from cst_captioning_tpu.train.schedule import make_optimizer
from cst_captioning_tpu.train.state import (
    TrainState,
    create_train_state,
    device_fold_in,
    device_key,
)
from cst_captioning_tpu.train.steps import batch_arrays, make_parallel_xe_step, make_xe_step
from cst_captioning_tpu.utils.logging import EventLogger
from cst_captioning_tpu.utils.profiling import StepProfiler


# run-plumbing fields expected to differ between the original run and a
# resumed one; excluded from drift detection so the alert stays meaningful
_VOLATILE_CONFIG_FIELDS = frozenset({
    "train.resume", "train.ckpt_dir", "train.profile_dir",
    "train.profile_steps", "train.debug_nans", "train.log_every_steps",
    "train.log_every",  # pre-rename snapshots carry the old field name
    # resilience plumbing: save cadence/rotation/rollback budget change how a
    # run survives faults, not what it computes (on_divergence/spike_factor
    # DO alter numerics under faults, so those two stay drift-tracked;
    # train.elastic also stays tracked — degraded vs strict changes what a
    # faulted run computes)
    "train.ckpt_every_steps", "train.keep_ckpts", "train.max_rollbacks",
    # elastic-health plumbing: where heartbeats go and how fast loss is
    # detected, not what the run computes
    "train.health", "train.health_dir", "train.health_interval_s",
    "train.peer_timeout_s", "train.health_misses", "train.health_sim_hosts",
    "train.dcn_stall_s",
    # observability plumbing: where the spans/metrics go, not what runs
    # (recorder/anomaly add metric OUTPUTS only — params stay bit-identical,
    # see train/steps._apply — so they are resume-volatile like obs itself)
    "train.obs", "train.obs_dir", "train.recorder_steps", "train.anomaly",
    "eval.results_json",
})


def _config_drift(saved: dict, current: dict, prefix: str = "") -> list[str]:
    """Dotted paths whose values differ between two JSON-born snapshots."""
    out: list[str] = []
    for key in sorted(set(saved) | set(current)):
        path = f"{prefix}{key}"
        if path in _VOLATILE_CONFIG_FIELDS:
            continue
        a, b = saved.get(key), current.get(key)
        if isinstance(a, dict) and isinstance(b, dict):
            out.extend(_config_drift(a, b, prefix=path + "."))
        elif a != b:
            out.append(path)
    return out


class Trainer:
    def __init__(
        self,
        cfg: ExperimentConfig,
        train_ds: CaptionDataset,
        val_ds: CaptionDataset | None = None,
        log_path: str = "",
        use_mesh: bool | None = None,
    ):
        self.cfg = cfg
        self.train_ds = train_ds
        self.val_ds = val_ds
        self.model = CaptionModel(cfg.model)
        self.log = EventLogger(log_path)
        # analytic FLOPs per teacher-forced XE row (obs/flops.py) — feeds
        # the run report's MFU column via the flops.xe.step counter
        self._xe_flops_per_row = _flops.model_xe_flops_per_row(cfg.model)
        if cfg.train.obs:
            obs_dir = cfg.train.obs_dir or os.path.join(
                cfg.train.ckpt_dir, "obs"
            )
            if multihost.is_multiprocess() and jax.process_index() != 0:
                # one stream per process (same contract as the JSONL log)
                obs_dir = os.path.join(obs_dir, f"proc{jax.process_index()}")
            obs.configure(
                obs_dir, run=cfg.name,
                snapshot_every=cfg.train.log_every_steps,
            )
            # the run report's MFU column divides the flops.<phase> counters
            # by the chip's published peak (obs/flops.py table, keyed on the
            # device kind). A kind without one (the CPU tests) sets no gauge
            # and the report's MFU cells read "not measured" (None)
            try:
                obs.gauge("device.peak_flops").set(
                    _flops.peak_flops(jax.devices()[0].device_kind)
                )
            except KeyError:
                pass
        # flight recorder (obs/recorder.py): per-step training-dynamics ring
        # + postmortem bundles. stats=True threads the extra on-device
        # update-ratio outputs through every step factory; the params math is
        # bit-identical either way (train/steps._apply), and recorder_steps=0
        # (default) builds literally the pre-recorder programs
        self._stats = bool(cfg.train.obs and cfg.train.recorder_steps > 0)
        # kept on self: spike_mode="adaptive" shares this detector's loss
        # Ewma with the sentinels built in _make_sentinel
        self._detector = (
            _anomaly.AnomalyDetector()
            if self._stats and cfg.train.anomaly else None
        )
        if self._stats:
            flight.configure(
                cfg.train.recorder_steps,
                obs_dir,
                run=cfg.name,
                detector=self._detector,
                config=cfg.to_dict(),
                # host identity: the fleet merge (obs/fleet.py) uses these to
                # name hosts and detect absent procs in a degraded merge
                proc=jax.process_index(),
                world=jax.process_count(),
            )
        # everything below (state init, resume restore, first collate) is
        # run setup: give it a span so the report's phase totals account for
        # the pre-training wall clock instead of reporting a coverage hole
        setup_span = obs.span("setup").begin()
        if cfg.train.debug_nans:
            # sanitizer mode (SURVEY.md §5 row 2): every jitted step re-runs
            # eagerly on NaN production and raises at the originating op
            jax.config.update("jax_debug_nans", True)

        n_dev = cfg.mesh.num_devices or len(jax.devices())
        sp = cfg.mesh.seq_devices > 1
        self.use_mesh = (n_dev > 1 or sp) if use_mesh is None else use_mesh
        self.mesh = (
            make_mesh(cfg.mesh.num_devices, seq_devices=cfg.mesh.seq_devices,
                      mp_devices=cfg.mesh.mp_devices)
            if self.use_mesh else None
        )
        # 2-D ('data','seq') mesh: batch shards over 'data', the FRAME axis
        # over 'seq' (collective attention softmax — the long-context layout)
        self.sp = self.mesh is not None and "seq" in self.mesh.axis_names
        if self.mesh is not None:
            n_data = self.mesh.shape["data"]
            if cfg.data.batch_size % n_data:
                # unlike eval (which wrap-pads exactly, evaluator.py), padding
                # a TRAINING batch would change how rows group into optimizer
                # steps — fail early with guidance, not a device_put error
                raise ValueError(
                    f"training batch_size {cfg.data.batch_size} must be "
                    f"divisible by the mesh's {n_data}-device 'data' axis; "
                    "pick a multiple or set mesh.num_devices/seq_devices"
                )
            if self.sp and cfg.model.max_frames % self.mesh.shape["seq"]:
                raise ValueError(
                    f"model.max_frames {cfg.model.max_frames} must be "
                    f"divisible by mesh.seq_devices {self.mesh.shape['seq']}"
                )
            if self.sp and multihost.is_multiprocess():
                multihost.assert_seq_axis_within_host(self.mesh.devices)

        # multi-host: each process collates only its slice of every global
        # batch (identical global order — the shuffle is epoch-keyed);
        # put_global below assembles the slices into globally-sharded arrays
        self.batcher = Batcher(
            train_ds,
            batch_size=cfg.data.batch_size,
            max_len=cfg.model.max_len,
            mode="caption",
            seq_per_vid=cfg.data.seq_per_vid,
            seed=cfg.data.shuffle_seed,
            host_shard=multihost.host_shard() if self.use_mesh else (0, 1),
        )
        # host staging for both phases' prefetched batches (sized by
        # data.prefetch, kept across epochs: a slot is first touched once)
        self._staging = StagingRing(cfg.data.prefetch)
        # the prefetch worker of the phase that is running, and the (phase,
        # mesh) its transform places for: _staged_epoch
        self._feed: PrefetchFeed | None = None
        self._feed_for: tuple | None = None
        self.steps_per_epoch = self.batcher.num_batches()
        tx = make_optimizer(cfg.train, self.steps_per_epoch)
        sample = next(iter(self.batcher.epoch(shuffle=False)))
        feats, masks, labels, *_ = batch_arrays(sample)
        self.state = create_train_state(
            self.model, tx, (feats, masks, labels), seed=cfg.train.seed
        )
        # the on-device finite-update guard rides with any active sentinel
        # policy (bit-identical on finite steps; "off" restores the exact
        # unguarded program)
        self.guard = cfg.train.on_divergence != "off"
        if self.mesh is not None:
            self.state = replicate(self.mesh, self.state)
        self._build_xe_step()

        if multihost.is_multiprocess():
            # verifiable evidence the cluster actually formed (a degraded
            # init would silently train N independent copies)
            self.log.log(
                "distributed",
                processes=jax.process_count(),
                process_index=jax.process_index(),
                devices=len(jax.devices()),
            )
        self.ckpt = CheckpointManager(
            cfg.train.ckpt_dir, metric="CIDEr-D", keep=cfg.train.keep_ckpts,
            log=self.log.log,
        )
        self.epoch = 0        # global epoch counter (batch-order key, logging)
        self.xe_epochs = 0    # per-phase progress: epochs-field budgets are
        self.rl_epochs = 0    # TOTALS, so a resumed run finishes the remainder
        # mid-epoch resume/rollback bookkeeping (resilience layer)
        self._resume_batch = 0     # XE batches to skip in the next epoch
        self._resume_rl_batch = 0  # RL batches to skip in the next epoch
        self._rollbacks = 0        # divergence rollbacks consumed this run
        self._rl_batcher: Batcher | None = None
        # drain-aware RL seam (README "Elastic training"): tokens the
        # pipelined loop decoded but never scored before a drain; replayed
        # by the resumed epoch so the seam batch is not re-decoded against
        # fresher params
        self._pending_seam: dict | None = None
        # elastic multi-host resilience (resilience/health.py): a heartbeat
        # monitor + peer-loss watchdog. The step loops poll `peer_lost` — a
        # plain Event read, no host<->device traffic — only when enabled.
        self.health: health_mod.HealthMonitor | None = None
        self._degraded_gen = 0
        self._all_mesh_devices = (
            list(self.mesh.devices.flat) if self.mesh is not None else None
        )
        self._initial_hosts = 1
        # pristine copies for the grow-back direction: _continue_degraded
        # overwrites the two working attributes above at every shrink, but a
        # regrow rebuilds host->device slices from the ORIGINAL layout
        self._original_mesh_devices = (
            None if self._all_mesh_devices is None
            else list(self._all_mesh_devices)
        )
        self._original_hosts = 1
        # a validated rejoiner awaiting admission at the next batch boundary
        self._regrow_host: int | None = None
        # name of the most recent step_* save — the drain seam the elastic
        # continuations restore by name (see restore_latest(prefer=...))
        self._last_step_ckpt: str | None = None
        if cfg.train.health:
            health_mod.set_dcn_stall_threshold(cfg.train.dcn_stall_s)
            num_hosts = cfg.train.health_sim_hosts or jax.process_count()
            self._initial_hosts = num_hosts
            self._original_hosts = num_hosts
            self.health = health_mod.HealthMonitor(
                cfg.train.health_dir
                or os.path.join(cfg.train.ckpt_dir, "health"),
                host_id=jax.process_index(),
                num_hosts=num_hosts,
                interval_s=cfg.train.health_interval_s,
                timeout_s=cfg.train.peer_timeout_s,
                misses=cfg.train.health_misses,
                log=self.log.log,
            ).start()
        if cfg.train.resume:
            self._resume()

        self._build_validator()
        setup_span.end()

    def _build_xe_step(self) -> None:
        """(Re)build the jitted XE step for the CURRENT mesh — called at init
        and again after a degraded-mesh rebuild."""
        cfg = self.cfg
        # the grad-allreduce spelling (parallel/comms.py): bucketing/dtype/
        # overlap from the train.comm_* knobs, shared with the RL update
        comm = CommConfig.from_train(cfg.train)
        # a new jitted step means the compile-time FLOPs probe must re-run
        # (a degraded-mesh rebuild changes the program)
        self._xe_cost = None
        if self.mesh is not None:
            if self.sp:
                # SP params are layout-identical to the plain model's, so the
                # state init above (plain model) feeds the SP step directly
                # donate=True: the step consumes self.state (rebound on every
                # call), so params + Adam moments update in place instead of
                # double-buffering — HBM headroom on the production path
                self.xe_step = make_sp_xe_step(
                    sp_model(cfg.model), self.mesh, cfg.train.label_smoothing,
                    data_axis="data", donate=True, guard=self.guard,
                    comm=comm, stats=self._stats,
                )
            else:
                self.xe_step = make_parallel_xe_step(
                    self.model, self.mesh, cfg.train.label_smoothing,
                    donate=True, guard=self.guard, comm=comm,
                    stats=self._stats,
                )
        else:
            self.xe_step = make_xe_step(
                self.model, cfg.train.label_smoothing, donate=True,
                guard=self.guard, comm=comm, stats=self._stats,
            )

    def _xe_flops_inc(self, rows, args) -> float:
        """Per-process FLOPs to count for one XE step. Prefers the COMPILED
        program's own cost (obs/flops.compiled_cost) so the MFU column and
        bench_comms agree on what a step costs; analytic per-row model when
        XLA exposes no cost or obs is off (the probe forces an AOT compile
        walk — skip it when nothing reads the counter). The compiled number
        is the whole (global-batch) program, split evenly across processes
        so per-process streams still sum to the global total; the analytic
        one counts this host's rows directly."""
        if self._xe_cost is None and obs.enabled():
            cost = _flops.compiled_cost(self.xe_step, *args)
            self._xe_cost = cost["flops"] if cost else False
            # probe bookkeeping: the counter ticks once per (re)compiled
            # program — a degraded-mesh rebuild re-probes and ticks again —
            # and the gauge labels which backend the MFU column reflects
            obs.counter("obs.flops.probes").inc()
            obs.gauge("flops.backend.xe.step").set(
                1.0 if self._xe_cost else 0.0
            )
        if self._xe_cost:
            return self._xe_cost / jax.process_count()
        return rows * self._xe_flops_per_row

    def _build_validator(self) -> None:
        cfg = self.cfg
        self.validator = (
            Evaluator(
                self.model,
                self.val_ds,
                EvalConfig(beam_size=1, max_len=cfg.model.max_len,
                           metrics=("CIDEr-D",)),
                batch_size=cfg.data.batch_size,
                mesh=self.mesh,
            )
            if self.val_ds is not None
            else None
        )

    def close(self) -> None:
        """Stop background machinery (the prefetch worker, the health
        watchdog, the flight recorder). Safe to call twice; the threads are
        daemons either way."""
        self._close_feed()
        if self.health is not None:
            self.health.stop()
        if self._stats:
            # orderly close: final flush, NO postmortem dump (crashes that
            # skip close() still dump via the recorder's atexit hook)
            flight.shutdown()

    # ---- resume / handoff --------------------------------------------------

    def _resume(self):
        # resume="auto": newest valid ckpt in this run's ckpt_dir;
        # resume=<dir>: explicit checkpoint directory (latest/best inside it)
        resume = self.cfg.train.resume
        src_dir = self.cfg.train.ckpt_dir if resume == "auto" else resume
        mgr = (
            self.ckpt if resume == "auto"
            else CheckpointManager(src_dir, log=self.log.log)
        )
        restored = mgr.restore_latest(jax.device_get(self.state))
        if restored is None:
            self.log.log("resume_not_found", dir=src_dir)
            return
        state, infos = restored
        batch_index, phase = self._adopt_restored(state, infos, src_dir)
        # surface config drift between the checkpoint and this run
        saved_cfg = infos.get("config")
        if saved_cfg:
            # one json round-trip canonicalizes tuples to lists, matching the
            # JSON-born saved snapshot leaf for leaf
            drift = _config_drift(saved_cfg, json.loads(self.cfg.to_json()))
            if drift:
                self.log.log("resume_config_drift", fields=drift)
        self.log.log(
            "resume", dir=src_dir, step=int(state.step), epoch=self.epoch,
            batch_index=batch_index, phase=phase or "epoch_end",
        )

    def _adopt_restored(self, state, infos: dict, src_dir: str) -> tuple[int, str]:
        """Install a restored state + its resume bookkeeping (shared by
        resume-at-startup and the degraded-mesh continuation). Returns the
        restored ``(batch_index, phase)``."""
        self.state = (
            replicate(self.mesh, state) if self.mesh is not None else state
        )
        self.epoch = int(infos.get("epoch", 0))
        # old checkpoints without phase counters: assume all epochs were XE
        self.xe_epochs = int(infos.get("xe_epochs", self.epoch))
        self.rl_epochs = int(infos.get("rl_epochs", 0))
        # exact data-order resume: epoch-keyed shuffling continues where the
        # uninterrupted run would have been. The caption batcher consumes one
        # epoch index per *shuffled* (XE) epoch only — RL epochs run their own
        # video-mode batcher — so the XE count, not the global one, is the key
        self.batcher.epoch_index = self.xe_epochs
        # mid-epoch checkpoint (preemption or step-interval save): the epoch
        # counters above are COMPLETED epochs; batch_index says how far into
        # the in-progress epoch the save happened, so the next phase call
        # replays exactly the remainder under the same epoch-keyed shuffle
        batch_index = int(infos.get("batch_index", 0))
        phase = infos.get("phase", "")
        self._resume_batch = self._resume_rl_batch = 0
        if batch_index and phase == "xe":
            self._resume_batch = batch_index
        elif batch_index and phase == "rl":
            self._resume_rl_batch = batch_index
        self.batcher.salt = int(infos.get("data_salt", 0))
        self._pending_seam = self._load_seam(src_dir, infos)
        return batch_index, phase

    # ---- drain-aware RL seam ------------------------------------------------

    @staticmethod
    def _seam_bytes(seam: dict, epoch: int, batch_index: int) -> bytes:
        """Serialize a captured seam (scst._seam_capture output, or the
        decoupled loop's in-flight ring) + its position as an npz blob for
        the checkpoint's extra_files."""
        arrays = {
            "epoch": np.asarray(int(epoch)),
            "batch_index": np.asarray(int(batch_index)),
        }
        if "ring" in seam:
            # decoupled drain: every in-flight rollout ring entry persists
            # (tokens + logprobs + RNG key data), flattened as ring{i}_*
            # entries are already host arrays (the capture device_gets);
            # np.savez converts the list/int leaves itself
            arrays["ring_n"] = len(seam["ring"])
            for i, e in enumerate(seam["ring"]):
                arrays[f"ring{i}_samples"] = e["samples"]
                arrays[f"ring{i}_lps"] = e["lps"]
                arrays[f"ring{i}_video_ids"] = [
                    str(v) for v in e["video_ids"]
                ]
                arrays[f"ring{i}_valid"] = e["valid"]
                arrays[f"ring{i}_rng"] = e["rng"]
                arrays[f"ring{i}_batch_index"] = int(e["batch_index"])
                if e.get("greedy") is not None:
                    arrays[f"ring{i}_greedy"] = e["greedy"]
        else:
            arrays["samples"] = np.asarray(seam["samples"])
            arrays["video_ids"] = np.asarray(
                [str(v) for v in seam["video_ids"]]
            )
            if seam.get("greedy") is not None:
                arrays["greedy"] = np.asarray(seam["greedy"])
        buf = io.BytesIO()
        np.savez(buf, **arrays)
        return buf.getvalue()

    def _load_seam(self, src_dir: str, infos: dict) -> dict | None:
        """Load the seam sidecar of the checkpoint that just restored (if
        its save drained a pipelined RL epoch, or closed an epoch whose
        drain had primed the next: the file names its own position)."""
        name = infos.get("ckpt_name", "")
        if not name:
            return None
        path = os.path.join(src_dir, name, "seam.npz")
        if not os.path.exists(path):
            return None
        try:
            with np.load(path, allow_pickle=False) as z:
                if "ring_n" in z.files:
                    # npz members load as host ndarrays already
                    ring = []
                    for i in range(int(z["ring_n"])):
                        e = {
                            "samples": z[f"ring{i}_samples"],
                            "lps": z[f"ring{i}_lps"],
                            "video_ids": [
                                str(v) for v in z[f"ring{i}_video_ids"]
                            ],
                            "valid": z[f"ring{i}_valid"],
                            "rng": z[f"ring{i}_rng"],
                            "batch_index": int(z[f"ring{i}_batch_index"]),
                        }
                        if f"ring{i}_greedy" in z.files:
                            e["greedy"] = z[f"ring{i}_greedy"]
                        ring.append(e)
                    seam = {
                        "ring": ring,
                        "epoch": int(z["epoch"]),
                        "batch_index": int(z["batch_index"]),
                    }
                else:
                    seam = {
                        "samples": np.asarray(z["samples"]),
                        "greedy": (
                            np.asarray(z["greedy"]) if "greedy" in z.files
                            else None
                        ),
                        "video_ids": [str(v) for v in z["video_ids"]],
                        "epoch": int(z["epoch"]),
                        "batch_index": int(z["batch_index"]),
                    }
        except (OSError, ValueError, KeyError) as e:
            # a torn/legacy seam degrades to the old re-decode behavior —
            # never to a crash or to silently wrong tokens
            self.log.log(
                "seam_unreadable", path=path, error=type(e).__name__,
                detail=str(e),
            )
            return None
        self.log.log(
            "seam_loaded", ckpt=name, epoch=seam["epoch"],
            batch_index=seam["batch_index"],
        )
        return seam

    def load_params_from(self, ckpt_dir: str, name: str = "best"):
        """XE -> RL handoff: params only, fresh optimizer (SURVEY.md §5)."""
        params = load_params(ckpt_dir, name, jax.device_get(self.state.params))
        self.state = self.state.replace(params=params)
        if self.mesh is not None:
            self.state = replicate(self.mesh, self.state)
        self.log.log("handoff", source=f"{ckpt_dir}/{name}")

    # ---- phases ------------------------------------------------------------

    def _batch_sharding(self):
        """device_put target for the XE batch tuple: a single axis-0 sharding
        (1-D mesh; a tree prefix for every element), or the per-leaf SP tuple
        (frames over 'seq', batch over 'data')."""
        if self.mesh is None:
            return None
        if self.sp:
            return sp_batch_shardings(self.mesh, self.cfg.model)
        return batch_sharding(self.mesh)

    def _device_batches(self, batcher: Batcher, skip: int = 0,
                        epochs: int = 1):
        """The XE epoch ``batcher.epoch_index``, staged to device, less its
        first ``skip`` batches; ``epochs`` is how many epochs the phase still
        has to run, this one included (:meth:`_staged_epoch`)."""
        shardings = self._batch_sharding()

        def transform(b):
            b = chaos.visit("xe.batch", b)
            if shardings is None:
                # valid rides along so wrap-padding rows get zero weight
                return batch_arrays(b) + (
                    jax.numpy.asarray(np.asarray(b.valid, np.float32)),
                )
            # keep the Batch's numpy arrays as-is: put_global transfers them
            # host->device exactly once, straight into the target sharding
            arrays = (
                b.feats, b.feat_masks, b.labels, b.mask, b.weights,
                np.asarray(b.valid, np.float32),
            )
            return multihost.put_global(shardings, arrays)

        return self._staged_epoch(
            "xe", batcher, skip, epochs, transform, place=shardings is None
        )

    def _rl_device_batches(self, batcher: Batcher, skip: int = 0,
                           epochs: int = 1):
        """Prefetched RL batches: arrays staged to device (sharded when a mesh
        is in play), video ids + valid mask staying host-side (this process's
        rows) for the reward. Arguments as :meth:`_device_batches`'s."""
        sharding = self._batch_sharding()
        if sharding is not None and self.sp:
            sharding = (sharding[0], sharding[1])  # (feats, masks) only

        def transform(b):
            b = chaos.visit("rl.batch", b)
            if sharding is not None:
                # numpy straight into the target sharding (single transfer)
                feats, masks = multihost.put_global(
                    sharding, (b.feats, b.feat_masks)
                )
            else:
                feats, masks = jax.device_put((b.feats, b.feat_masks))
            return (feats, masks, b.video_ids, b.valid)

        return self._staged_epoch(
            "rl", batcher, skip, epochs, transform, place=False
        )

    def _staged_epoch(self, phase: str, batcher: Batcher, skip: int,
                      epochs: int, transform, place: bool):
        """One epoch's batches from the phase's prefetch feed: one worker
        for the whole phase, which stages the next epoch's first batches
        while this one drains (data/prefetch.py). The epoch is named by what
        decides its batches: the batcher, its salt, the epoch index the main
        thread pinned, ``skip`` (a mid-epoch resume drops the first batches
        of the, already deterministic, order before any transform or
        transfer), and the index at which the phase ends. A rollback's
        re-salt and rewind, a rebuilt batcher or a resume therefore ask for
        another epoch than the one staged ahead, and the feed starts over."""
        made_for = (phase, self.mesh)   # what ``transform`` places for
        if self._feed is None or self._feed_for != made_for:
            self._close_feed()
            self._feed = PrefetchFeed(
                lambda key: key.batches(self._staging), EpochKey.following,
                size=self.cfg.data.prefetch, transform=transform, place=place,
                staging=self._staging,
            )
            self._feed_for = made_for
        index = batcher.epoch_index
        return self._feed.epoch(
            EpochKey(batcher, batcher.salt, index, skip, index + epochs)
        )

    def _close_feed(self) -> None:
        feed, self._feed, self._feed_for = self._feed, None, None
        if feed is not None:
            feed.close()

    # ---- resilience helpers ------------------------------------------------

    def _make_sentinel(self, phase: str) -> DivergenceSentinel:
        """Policy/cadence from config: the default ``skip_batch`` policy
        defers every readback to epoch ends / save points (zero extra host
        syncs — the on-device guard already excluded the bad update);
        ``rollback``/``abort`` buy mid-epoch detection for one amortized
        device_get per 32 steps."""
        cfg = self.cfg.train
        adaptive = None
        if cfg.spike_mode == "adaptive" and cfg.spike_factor:
            # the feedback loop (resilience/adaptive.py): the anomaly
            # detector's loss Ewma — updated on the recorder's flush cadence
            # — sets the spike bound; without a detector the thresholds own
            # a private Ewma fed from the sentinel's flushes
            adaptive = AdaptiveThresholds(
                factor_max=cfg.spike_factor,
                factor_min=cfg.spike_factor_min,
                ewma=(
                    self._detector.ewma("loss")
                    if self._detector is not None else None
                ),
            )
        return DivergenceSentinel(
            policy=cfg.on_divergence,
            phase=phase,
            log=self.log.log,
            spike_factor=cfg.spike_factor,
            check_every=32 if cfg.on_divergence in ("rollback", "abort") else None,
            adaptive=adaptive,
        )

    def _ckpt_infos(self, phase: str = "", batch_index: int = 0,
                    step_no: int | None = None) -> dict:
        return {
            "epoch": self.epoch,
            "xe_epochs": self.xe_epochs,
            "rl_epochs": self.rl_epochs,
            "phase": phase,
            "batch_index": batch_index,
            "global_step": step_no,
            "data_salt": self.batcher.salt,
            "config": self.cfg.to_dict(),
        }

    def _save_step_ckpt(self, phase: str, step_no: int, batch_index: int,
                        seam: dict | None = None) -> None:
        """Mid-epoch checkpoint (step-interval or preemption-triggered):
        records the exact batch index so resume replays the epoch remainder.
        ``seam`` (drain-aware RL saves) rides along as ``seam.npz`` in the
        same atomic swap."""
        if jax.process_index() == 0:
            extra = None
            if seam:
                # tokens decoded while priming are the next epoch's batch 0
                at = (
                    (self.epoch + 1, 0) if seam.get("next_epoch")
                    else (self.epoch, batch_index)
                )
                extra = {"seam.npz": self._seam_bytes(seam, *at)}
            with obs.span("ckpt", kind="step"):
                with obs.span("ckpt.readback"):
                    host_state = host_copy(self.state)
                self.ckpt.save_step(
                    host_state, step_no,
                    self._ckpt_infos(phase, batch_index, step_no),
                    extra_files=extra,
                )
        # the elastic continuations restore THIS save by name: its
        # phase-local step ordinal may rank below an older epoch-end ckpt
        self._last_step_ckpt = f"step_{int(step_no):08d}"
        self.log.log(
            "ckpt_step", phase=phase, step=step_no, batch_index=batch_index,
            seam=bool(seam),
        )

    def _preempt_save(self, phase: str, step_no: int, batch_index: int,
                      sentinel: DivergenceSentinel,
                      seam: dict | None = None) -> None:
        """SIGTERM landed: flush pending divergence checks (never checkpoint
        an update the sentinel would have rejected), save mid-epoch, make the
        event log durable, and unwind via :class:`Preempted`."""
        sentinel.flush()
        # postmortem before the unwind: the bundle captures the ring as of
        # the drained step (postmortem self-flushes the recorder)
        flight.postmortem("preempt", phase=phase, step=step_no)
        self._save_step_ckpt(phase, step_no, batch_index, seam=seam)
        self.log.log(
            "preempt", phase=phase, step=step_no, batch_index=batch_index,
        )
        self.log.flush()
        raise Preempted(
            f"preempted at {phase} step {step_no} "
            f"(epoch {self.epoch + 1}, batch {batch_index}); "
            "checkpoint saved — rerun with train.resume='auto'"
        )

    def _peer_loss_save(self, phase: str, step_no: int, batch_index: int,
                        sentinel: DivergenceSentinel,
                        seam: dict | None = None) -> None:
        """A peer host was lost (heartbeat timeout / partial preemption):
        coordinated DRAIN — the in-flight step finished, prefetch is about
        to be flushed by the epoch unwind — then a durable mid-epoch save in
        drain-aware order, then :class:`PeerLost` so the caller picks
        degraded continuation or the strict full-restart fallback."""
        sentinel.flush()
        # lost hosts computed BEFORE the dump so the bundle meta names the
        # victim(s) — the fleet merge reads `lost` for trip attribution
        lost = self.health.lost()
        flight.postmortem("peer_loss", phase=phase, step=step_no, lost=lost)
        self._save_step_ckpt(phase, step_no, batch_index, seam=seam)
        obs.counter("resilience.peer_loss_drain").inc()
        self.log.log(
            "peer_loss_drain", phase=phase, step=step_no,
            batch_index=batch_index, lost=lost,
        )
        self.log.flush()
        raise PeerLost(
            lost,
            f"lost host(s) {lost} at {phase} step {step_no} "
            f"(epoch {self.epoch + 1}, batch {batch_index}); drained and "
            "saved — continuing degraded or restart with train.resume='auto'",
        )

    def _apply_rollback(self, phase: str, err: RollbackRequested,
                        sentinel: DivergenceSentinel) -> None:
        """Divergence rollback: restore the newest verifiable checkpoint and
        re-randomize the data order (salted epoch-keyed shuffle), so the
        replayed epochs don't march straight back into the same poison batch
        sequence. Budgeted by ``train.max_rollbacks``."""
        self._rollbacks += 1
        obs.counter("resilience.rollback").inc()
        # no postmortem here: the sentinel already dumped the ring at the
        # divergence itself (reason=divergence_<kind>, action=rollback) —
        # a second dump would hold the identical ring and burn dump budget
        if self._rollbacks > self.cfg.train.max_rollbacks:
            raise TrainingDiverged(
                f"rollback budget exhausted ({self.cfg.train.max_rollbacks}) "
                f"after {phase} divergence: {err}"
            ) from err
        restored = self.ckpt.restore_latest(jax.device_get(self.state))
        if restored is None:
            raise TrainingDiverged(
                f"{phase} diverged with no checkpoint to roll back to: {err}"
            ) from err
        state, infos = restored
        self.state = (
            replicate(self.mesh, state) if self.mesh is not None else state
        )
        self.epoch = int(infos.get("epoch", 0))
        self.xe_epochs = int(infos.get("xe_epochs", self.epoch))
        self.rl_epochs = int(infos.get("rl_epochs", 0))
        # the in-progress epoch restarts from batch 0 under the new salt (a
        # mid-epoch checkpoint's batch_index indexes the OLD order — it no
        # longer names the same batches, so it must not be replayed; ditto
        # any pending seam tokens, which belong to the old order)
        self._resume_batch = self._resume_rl_batch = 0
        self._pending_seam = None
        self.batcher.salt = self._rollbacks
        if self._rl_batcher is not None:
            self._rl_batcher.salt = self._rollbacks
        sentinel.reset()
        self.log.log(
            "rollback",
            phase=phase,
            step=err.step,
            kind=err.kind,
            restored_step=infos.get("global_step"),
            restored_epoch=self.epoch,
            salt=self._rollbacks,
        )

    # ---- degraded-mesh continuation -----------------------------------------

    def _surviving_devices(self, survivors: list[int], devices=None,
                           hosts: int | None = None) -> list:
        """Devices of the given hosts, in the original mesh order.

        Real multi-process clusters map hosts to ``device.process_index``;
        simulated hosts (train.health_sim_hosts) split the mesh's device
        list evenly — host k owns the k-th contiguous chunk. The default
        base is the CURRENT layout; the regrow path passes the pristine
        ``_original_mesh_devices``/``_original_hosts`` so a re-admitted
        host's slice comes back in its original position."""
        devices = self._all_mesh_devices if devices is None else devices
        hosts = self._initial_hosts if hosts is None else hosts
        if multihost.is_multiprocess():
            alive = set(survivors)
            return [d for d in devices if d.process_index in alive]
        per_host = max(1, len(devices) // hosts)
        out = []
        for h in survivors:
            out.extend(devices[h * per_host:(h + 1) * per_host])
        return out

    def _continue_degraded(self, phase: str, err: PeerLost) -> None:
        """Elastic continuation after a drained peer loss: rendezvous the
        survivors (retry/timeout/backoff), rebuild a SHRUNK 1-D data mesh
        over the surviving devices, reshard params + optimizer state from
        the last durable checkpoint (the drain just wrote one, seam
        included), rescale the per-host batch share, and let the phase loop
        replay the epoch remainder."""
        cfg = self.cfg
        if self.health is None or self._all_mesh_devices is None:
            raise err  # elastic continuation needs the monitor AND a mesh
        if self.sp:
            raise RuntimeError(
                "degraded-mesh continuation does not support the "
                "('data','seq') mesh — a lost host takes part of every seq "
                "row with it; run elastic='strict' with seq_devices > 1"
            ) from err
        self._degraded_gen += 1
        expected = self.health.survivors()
        with obs.span("degraded_rendezvous", generation=self._degraded_gen):
            survivors = health_mod.rendezvous(
                self.health.dir,
                host_id=self.health.host_id,
                hosts=expected,
                generation=self._degraded_gen,
                timeout_s=max(cfg.train.peer_timeout_s * 4.0, 1.0),
            )
        devices = self._surviving_devices(survivors)
        n_data = len(devices)
        if n_data == 0:
            raise RuntimeError(
                f"no devices survive the loss of host(s) {err.hosts}"
            ) from err
        if cfg.data.batch_size % n_data:
            raise RuntimeError(
                f"cannot continue degraded: global batch_size "
                f"{cfg.data.batch_size} is not divisible by the {n_data} "
                "surviving devices — run elastic='strict' or pick a batch "
                "size divisible by every survivable mesh width"
            ) from err
        self.mesh = Mesh(np.asarray(devices), ("data",))
        # per-host batch rescaling: the GLOBAL batch is unchanged, each
        # surviving host's share grows to cover the lost host's rows
        if multihost.is_multiprocess():
            shard = (survivors.index(jax.process_index()), len(survivors))
            self.batcher = self._rebuild_batcher(self.batcher, shard)
        # reshard params + optimizer state from the last durable checkpoint
        # onto the shrunk mesh (the peer-loss drain saved one moments ago,
        # with the exact batch index + pipeline seam — prefer it by NAME:
        # its phase-local step ordinal may rank below an epoch-end save)
        restored = self.ckpt.restore_latest(
            jax.device_get(self.state), prefer=self._last_step_ckpt
        )
        if restored is None:
            raise RuntimeError(
                "degraded continuation found no restorable checkpoint in "
                f"{cfg.train.ckpt_dir} — the peer-loss drain save is missing"
            ) from err
        state, infos = restored
        batch_index, res_phase = self._adopt_restored(
            state, infos, cfg.train.ckpt_dir
        )
        self._build_xe_step()
        self._build_validator()
        self.health.set_membership(survivors)
        self.health.acknowledge()
        # sync the monitor's generation: rejoin markers for the NEXT regrow
        # round are stamped generation+1 (stale ones are refused)
        self.health.generation = self._degraded_gen
        self._all_mesh_devices = devices
        self._initial_hosts = len(survivors)
        obs.counter("resilience.degraded_continuation").inc()
        obs.event(
            "degraded_mesh", phase=phase, lost=err.hosts,
            survivors=survivors, devices=n_data,
        )
        # the elastic-timeline spelling (obs/fleet.py pairs shrink→regrow
        # arcs): one event per victim so every arc names a single host
        for victim in err.hosts:
            obs.event(
                "mesh_shrink", phase=phase, victim=victim, devices=n_data,
                generation=self._degraded_gen,
            )
        self.log.log(
            "degraded_mesh",
            phase=phase,
            lost=err.hosts,
            survivors=survivors,
            devices=n_data,
            global_batch=cfg.data.batch_size,
            resumed_phase=res_phase,
            resumed_batch_index=batch_index,
        )

    # ---- elastic grow-back (host re-admission) ------------------------------

    def _poll_rejoin(self) -> None:
        """Batch-boundary rejoin poll — the grow-back half of README
        "Elastic training". Free unless the run is degraded with regrow
        enabled (a couple of attribute reads); only then does it visit the
        ``health.rejoin`` chaos point and scan for rejoin markers. A
        readable marker triggers liveness validation under the budgeted
        retry policy: success schedules admission at the next batch
        boundary (``_regrow_host``), failure consumes the marker and leaves
        the degraded run untouched."""
        h = self.health
        if (
            h is None
            or self._regrow_host is not None
            or self.cfg.train.elastic != "degraded"
            or not self.cfg.train.elastic_regrow
            or self._original_mesh_devices is None
            or not h.lost_hosts
            or h.peer_lost  # an unacknowledged loss outranks a rejoin
        ):
            return
        chaos.visit("health.rejoin")
        pending = h.pending_rejoins()
        if not pending:
            return
        host = min(pending)  # deterministic order when several announce
        gen = self._degraded_gen + 1
        try:
            health_mod.attempt_rejoin(h, host, gen)
        except health_mod.RejoinRefused as e:
            h.clear_rejoin(host)
            obs.event("rejoin_refused", host=host, generation=gen)
            self.log.log(
                "rejoin_refused", host=host, generation=gen, detail=str(e),
            )
            return
        self._regrow_host = host

    def _regrow_save(self, phase: str, step_no: int, batch_index: int,
                     sentinel: DivergenceSentinel,
                     seam: dict | None = None) -> None:
        """A validated rejoiner is waiting: coordinated DRAIN at the batch
        boundary — mirror of the peer-loss drain, seam included, so the
        admission never tears a pipelined update — then :class:`HostRejoin`
        unwinds to the phase loop, which runs the regrow rendezvous."""
        host = self._regrow_host
        sentinel.flush()
        self._save_step_ckpt(phase, step_no, batch_index, seam=seam)
        obs.counter("resilience.regrow_drain").inc()
        self.log.log(
            "regrow_drain", phase=phase, step=step_no,
            batch_index=batch_index, rejoiner=host,
        )
        self.log.flush()
        raise health_mod.HostRejoin(
            host,
            f"host {host} re-admission scheduled at {phase} step {step_no} "
            f"(epoch {self.epoch + 1}, batch {batch_index}); drained and "
            "saved",
        )

    def _continue_regrown(self, phase: str,
                          err: health_mod.HostRejoin) -> bool:
        """Elastic grow-back: the inverse of :meth:`_continue_degraded`.

        Survivors and the rejoiner rendezvous at the bumped generation,
        the FULL 1-D data mesh is rebuilt from the pristine device layout,
        params + optimizer state reshard onto it via the ``replicate`` /
        ``put_full_global`` path from the drain checkpoint the SURVIVORS
        just wrote (the rejoiner never trusts its own stale checkpoint),
        per-host batch shares rescale back (global batch unchanged), the
        jitted closures rebuild, and the phase loop replays the epoch
        remainder — seam included. Returns True on admission; False when
        the rendezvous timed out or the grown mesh cannot carry the batch,
        in which case the degraded run continues exactly where the drain
        left it, untouched (never a second outage)."""
        cfg = self.cfg
        host = err.host
        self._regrow_host = None
        gen = self._degraded_gen + 1
        members = sorted(set(self.health.survivors()) | {host})
        devices = self._surviving_devices(
            members, devices=self._original_mesh_devices,
            hosts=self._original_hosts,
        )
        n_data = len(devices)
        admitted = False
        refuse_reason = ""
        if cfg.data.batch_size % n_data:
            refuse_reason = (
                f"global batch_size {cfg.data.batch_size} is not divisible "
                f"by the {n_data} regrown devices"
            )
        else:
            try:
                with obs.span("regrow_rendezvous", generation=gen):
                    health_mod.rendezvous(
                        self.health.dir,
                        host_id=self.health.host_id,
                        hosts=members,
                        generation=gen,
                        timeout_s=max(cfg.train.peer_timeout_s * 2.0, 0.5),
                    )
                admitted = True
            except health_mod.RendezvousTimeout as e:
                # the flaky rejoiner: announced, validated, then died
                # before checking in — time out and stay degraded
                refuse_reason = str(e)
        if admitted:
            self.mesh = Mesh(np.asarray(devices), ("data",))
            if multihost.is_multiprocess():
                shard = (members.index(jax.process_index()), len(members))
                self.batcher = self._rebuild_batcher(self.batcher, shard)
            self.health.readmit(host)
            self.health.set_membership(members)
            self._degraded_gen = gen
            self.health.generation = gen
            self._all_mesh_devices = devices
            self._initial_hosts = len(members)
        else:
            obs.counter("resilience.regrow.refused").inc()
            self.health.clear_rejoin(host)
            obs.event(
                "regrow_refused", phase=phase, rejoiner=host, generation=gen,
            )
            self.log.log(
                "regrow_refused", phase=phase, rejoiner=host, generation=gen,
                detail=refuse_reason,
            )
        # state from the SURVIVORS: the regrow drain saved the survivor
        # state moments ago; restoring that checkpoint (by NAME — its
        # phase-local step ordinal may rank below an epoch-end save) and
        # replicating onto self.mesh (full when admitted, unchanged when
        # refused) is the state handoff AND re-arms the mid-epoch resume
        # bookkeeping (batch index + pipeline seam) either way
        restored = self.ckpt.restore_latest(
            jax.device_get(self.state), prefer=self._last_step_ckpt
        )
        if restored is None:
            raise RuntimeError(
                "regrow continuation found no restorable checkpoint in "
                f"{cfg.train.ckpt_dir} — the regrow drain save is missing"
            ) from err
        state, infos = restored
        batch_index, res_phase = self._adopt_restored(
            state, infos, cfg.train.ckpt_dir
        )
        if admitted:
            self._build_xe_step()
            self._build_validator()
            obs.counter("resilience.regrow.admitted").inc()
            obs.event(
                "mesh_regrow", phase=phase, rejoiner=host, devices=n_data,
                generation=gen,
            )
            self.log.log(
                "mesh_regrow",
                phase=phase,
                rejoiner=host,
                hosts=members,
                devices=n_data,
                generation=gen,
                global_batch=cfg.data.batch_size,
                resumed_phase=res_phase,
                resumed_batch_index=batch_index,
            )
        return admitted

    def _rebuild_batcher(self, old: Batcher, host_shard: tuple[int, int]) -> Batcher:
        """Same data order, new host share (degraded multi-process only)."""
        new = Batcher(
            self.train_ds,
            batch_size=old.batch_size,
            max_len=old.max_len,
            mode=old.mode,
            seq_per_vid=old.seq_per_vid,
            seed=old.seed,
            host_shard=host_shard,
        )
        new.epoch_index = old.epoch_index
        new.salt = old.salt
        return new

    # ---- XE phase ----------------------------------------------------------

    def train_xe(self, epochs: int | None = None) -> float | None:
        """Cross-entropy (XE/WXE) phase; returns last validation CIDEr-D.

        ``epochs=None`` treats ``cfg.train.epochs`` as the phase TOTAL: a
        resumed run trains only the remainder (including the remainder of a
        mid-epoch preempted epoch). An explicit ``epochs`` runs exactly that
        many more. Raises :class:`Preempted` after a SIGTERM-triggered save,
        :class:`TrainingDiverged` under the abort policy / exhausted
        rollback budget.
        """
        cfg = self.cfg
        if epochs is None:
            epochs = max(0, cfg.train.epochs - self.xe_epochs)
        if epochs == 0:
            return None
        target = self.xe_epochs + epochs
        meter = obs.StepMeter("xe")
        profiler = StepProfiler(
            os.path.join(cfg.train.profile_dir, "xe") if cfg.train.profile_dir
            else "",
            cfg.train.profile_steps,
            log=self.log.log,
        )
        sentinel = self._make_sentinel("xe")
        last_val = None
        run = {"first_step": True}  # compile-step meter exclusion, phase-wide
        try:
            with PreemptionHandler() as pre:
                while self.xe_epochs < target:
                    try:
                        last_val = self._xe_epoch(
                            meter, profiler, sentinel, pre, run,
                            epochs_left=target - self.xe_epochs,
                        )
                    except RollbackRequested as e:
                        self._apply_rollback("xe", e, sentinel)
                    except PeerLost as e:
                        # strict keeps today's abort-and-full-restart (the
                        # saved drain resumes bit-exactly on the full mesh);
                        # degraded shrinks the mesh and keeps training on
                        # the survivors
                        if self.cfg.train.elastic != "degraded":
                            raise
                        self._continue_degraded("xe", e)
                        run["first_step"] = True  # recompile on the shrunk mesh
                    except health_mod.HostRejoin as e:
                        if self._continue_regrown("xe", e):
                            run["first_step"] = True  # recompile on the full mesh
        finally:
            # left by an exception raised past an epoch's last batch (a save
            # at the epoch's end): the worker is on the next epoch by then
            self._close_feed()
        return last_val

    def _xe_epoch(self, meter, profiler, sentinel, pre, run,
                  epochs_left: int = 1) -> float | None:
        """One XE epoch (possibly a resumed remainder): step loop, sentinel,
        mid-epoch saves, epoch-end validation + checkpoint. ``epochs_left``:
        the phase's epochs still to run, this one included (the prefetch
        worker stages ahead into the next one, and not past the last)."""
        cfg = self.cfg
        weighted = cfg.train.loss == "wxe"
        log_every = cfg.train.log_every_steps
        ckpt_every = cfg.train.ckpt_every_steps
        # pin the batch-order key: epochs 0..xe_epochs-1 are complete, this
        # epoch replays/starts index xe_epochs (idempotent under rollback)
        self.batcher.epoch_index = self.xe_epochs
        skip = self._resume_batch
        self._resume_batch = 0
        batch_no = skip
        # host-side step counter: reading int(self.state.step) per step in
        # the loop would block on the just-dispatched update every step
        step_no = int(self.state.step)
        if obs.enabled():
            obs.set_context(phase="xe", epoch=self.epoch + 1)
        meter.begin_epoch()
        losses = []
        batches = self._device_batches(self.batcher, skip=skip,
                                       epochs=epochs_left)
        # xe.step spans cover the loop body (dispatch + bookkeeping) and
        # prefetch.wait the host's wait on the input pipeline, so the report
        # splits compute-bound from data-bound epochs; the xe.epoch span's
        # SELF time is the unattributed remainder (the epoch-end flushes)
        with obs.span("xe.epoch"):
            try:
                for arrays in batches:
                    with obs.span("xe.step"):
                        feats, masks, labels, mask, weights, valid = arrays
                        # invalid rows get zero weight -> excluded from loss
                        weights = valid if not weighted else weights * valid
                        self.state, m = self.xe_step(
                            self.state, feats, masks, labels, mask, weights
                        )
                        # keep the device scalar: float() here would sync per
                        # step (graftlint GL001); the epoch summary reads
                        # them all back in one device_get
                        losses.append(m["loss"])
                        # record before push: a sentinel trip's postmortem
                        # self-flushes, so the ring always includes the
                        # diverged step (flight.record keeps device scalars
                        # — zero sync, same contract as sentinel.push)
                        flight.record(step_no + 1, "xe", m)
                        sentinel.push(step_no + 1, m["loss"], m.get("nonfinite"))
                        step_no += 1
                        batch_no += 1
                        if obs.enabled():
                            obs.set_context(step=step_no)
                        if log_every and step_no % log_every == 0:
                            # per-step event: a mid-epoch divergence (NaN,
                            # grad blowup) is locatable from the log alone
                            # (SURVEY.md §5); the float() syncs are gated —
                            # amortized over log_every steps
                            self.log.log(
                                "xe_step",
                                phase="xe",
                                step=step_no,
                                epoch=self.epoch + 1,
                                loss=float(m["loss"]),
                                grad_norm=float(m["grad_norm"]),
                            )
                            # ride the same gate: ONE batched device_get
                            # drains the recorder's pending scalars
                            flight.flush()
                        obs.maybe_snapshot(step_no)
                        profiler.tick()
                        meter.tick(cfg.data.batch_size, first=run["first_step"])
                        run["first_step"] = False
                        # self.state is the step's OUTPUT here (same shapes;
                        # the donated input is already consumed) — safe to
                        # lower against for the one-time cost probe
                        obs.counter("flops.xe.step").inc(
                            self._xe_flops_inc(cfg.data.batch_size, (
                                self.state, feats, masks, labels, mask,
                                weights,
                            ))
                        )
                        chaos.visit("xe.step")
                        if self.health is not None:
                            self.health.note_step(step_no)
                        if pre.requested:
                            self._preempt_save("xe", step_no, batch_no, sentinel)
                        if self.health is not None and self.health.peer_lost:
                            self._peer_loss_save(
                                "xe", step_no, batch_no, sentinel
                            )
                        self._poll_rejoin()
                        if self._regrow_host is not None:
                            self._regrow_save("xe", step_no, batch_no, sentinel)
                        if ckpt_every and step_no % ckpt_every == 0:
                            # never save an update the policy rejects
                            flight.flush()
                            sentinel.flush()
                            self._save_step_ckpt("xe", step_no, batch_no)
            finally:
                # left before its end (a preemption or peer-loss save, a
                # rollback, an error): the prefetch worker retires with what
                # it staged; consumed to its end: it is on the next epoch
                batches.close()
            profiler.stop()
            # a SIGTERM that lands between the last step and here must not let
            # the epoch counters advance past the state actually saved
            if pre.requested:
                self._preempt_save("xe", step_no, batch_no, sentinel)
            if self.health is not None and self.health.peer_lost:
                self._peer_loss_save("xe", step_no, batch_no, sentinel)
            self._poll_rejoin()
            if self._regrow_host is not None:
                self._regrow_save("xe", step_no, batch_no, sentinel)
            flight.flush()
            sentinel.flush()
        self.epoch += 1
        self.xe_epochs += 1
        vals = np.asarray(jax.device_get(losses), np.float64)
        vals = vals[np.isfinite(vals)]  # guard-skipped steps carry NaN losses
        self.log.log(
            "xe_epoch",
            epoch=self.epoch,
            # ONE readback for the whole epoch's loss scalars
            loss=float(vals.mean()) if vals.size else float("nan"),
            **meter.epoch_summary(),
        )
        obs.snapshot_metrics(epoch=self.epoch)
        return self._validate_and_checkpoint(step_no)

    def train_rl(self, epochs: int | None = None) -> float | None:
        """CST/RL phase (SCST or consensus-CST per cfg.rl).

        ``epochs=None``: ``cfg.rl.epochs`` is the phase TOTAL (see train_xe).

        Resilience mirrors the XE loop: divergence sentinel on every update,
        SIGTERM (or a detected peer loss) stops the epoch at the next batch
        boundary and the pipeline drains in SCHEDULE ORDER: the saved state
        matches exactly ``batch_index`` completed steps, and the pipelined
        loop additionally decodes the seam batch at its exact pipeline
        position and persists the tokens (``seam.npz``) next to the state.
        A mid-epoch resume replays the remainder of the epoch and the seam
        tokens, so BOTH ``rl.pipelined`` modes resume bit-identically to the
        uninterrupted run (previously the pipelined resume re-decoded the
        seam batch against params one update fresher).

        The pipelined loop runs on ACROSS an epoch's end
        (``SCSTTrainer.train_epoch``, ``next_epoch``): where the phase has a
        next epoch, this epoch's drain decodes that epoch's first two
        batches (from its own staged batches and its own folded key) under
        its last two updates, so that no epoch begins with an empty
        pipeline. Every batch is then decoded one update stale, an epoch's
        first included; each is scored once and applied once, in order, and
        a step of epoch e+1 is counted in epoch e+1. At an epoch's end
        ``self.state`` holds exactly that epoch's updates: validation, the
        ``rl_epoch`` line and the checkpoint see what a loop cut at the
        epoch would show them, the state's read-back runs under the decode
        still queued, and the checkpoint carries the next epoch's first
        batch's tokens (``seam.npz`` at position (epoch + 1, 0): that batch
        was decoded one update before the saved state), so a resume from an
        epoch-end checkpoint is bit-identical too. The phase's last epoch,
        ``rl.pipelined=False`` and the decoupled topology drain as ever; a
        stop while priming saves the state with that seam; a rollback, a
        degraded or regrown mesh and a rebuilt batcher drop the primed pair
        with the staged batches (``SCSTTrainer.drop_primed``).
        """
        cfg = self.cfg
        if epochs is None:
            epochs = max(0, cfg.rl.epochs - self.rl_epochs)
        if epochs == 0:
            return None
        rl_setup = obs.span("setup", phase="rl").begin()
        tx = make_optimizer(cfg.train, self.steps_per_epoch, lr_override=cfg.rl.lr)
        if self.rl_epochs == 0:
            # XE -> RL transition: fresh optimizer at RL LR (handoff semantics)
            # device_put, not jnp.zeros: the reset step counter must reach
            # the device via an EXPLICIT transfer, and tx.init runs jitted
            # so its zero-moments materialize on device without staging
            # eager scalar constants (sanitizer gate holds the RL hot loop
            # under jax.transfer_guard("disallow"))
            self.state = self.state.replace(
                step=jax.device_put(np.zeros((), np.int32)),
                opt_state=jax.jit(tx.init)(self.state.params),
                tx=tx,
            )
            if self.mesh is not None:
                self.state = replicate(self.mesh, self.state)
        else:
            # resumed mid-RL: the restored opt_state/step already belong to the
            # RL optimizer (saved during RL) — keep the Adam moments and
            # schedule position, just re-attach the non-serialized tx. The
            # structures must match (make_optimizer differs only in LR value);
            # verify rather than assume, so a future phase-specific optimizer
            # change cannot silently misinterpret the restored moments
            fresh = jax.eval_shape(tx.init, self.state.params)
            if jax.tree.structure(fresh) != jax.tree.structure(self.state.opt_state):
                raise RuntimeError(
                    "mid-RL resume: the checkpoint's opt_state tree does not "
                    "match the RL optimizer built from this config — the "
                    "restored Adam moments would be misinterpreted. Did the "
                    "optimizer definition change between runs?"
                )
            self.state = self.state.replace(tx=tx)

        # df=None lets RewardComputer build the train-pool df itself
        df = CorpusDF.load(cfg.data.cider_df) if cfg.data.cider_df else None
        reward = RewardComputer(
            self.train_ds.vocab,
            self.train_ds.gts_pool(),
            df=df,
            cider_weight=cfg.rl.reward_cider_weight,
            bleu_weight=cfg.rl.reward_bleu4_weight,
            bleu_scale=cfg.rl.reward_bleu4_scale,
            num_threads=cfg.rl.reward_threads,
        )
        # name the scorer: a failed g++ build degrades to the much slower
        # Python scorer, which must be visible in the log, not inferred
        self.log.log(
            "reward_scorer", scorer=reward.scorer, error=reward.native_error,
        )

        def build_scst():
            """SCST step closures + batcher for the CURRENT mesh — rebuilt
            after a degraded-mesh continuation shrinks it."""
            if cfg.train.rl_topology == "decoupled":
                # actor/learner split epoch schedule (rl/async_scst.py);
                # batch_size clamps the submesh split to batch divisors
                scst = AsyncSCSTTrainer(
                    self.model, reward, cfg.rl, mesh=self.mesh,
                    max_len=cfg.model.max_len, donate=True,
                    guard=self.guard, on_event=self.log.log,
                    comm=CommConfig.from_train(cfg.train),
                    stats=self._stats, batch_size=cfg.data.batch_size,
                )
            else:
                scst = SCSTTrainer(
                    self.model, reward, cfg.rl, mesh=self.mesh,
                    max_len=cfg.model.max_len, donate=True, guard=self.guard,
                    on_event=self.log.log,
                    comm=CommConfig.from_train(cfg.train),
                    stats=self._stats,
                )
            rl_batcher = Batcher(
                self.train_ds,
                batch_size=cfg.data.batch_size,
                max_len=cfg.model.max_len,
                mode="video",
                seed=cfg.data.shuffle_seed,
                host_shard=self.batcher.host_shard if self.use_mesh else (0, 1),
            )
            rl_batcher.salt = self.batcher.salt
            return scst, rl_batcher

        scst, rl_batcher = build_scst()
        self._rl_batcher = rl_batcher
        target = self.rl_epochs + epochs
        meter = obs.StepMeter("rl")
        profiler = StepProfiler(
            os.path.join(cfg.train.profile_dir, "rl") if cfg.train.profile_dir
            else "",
            cfg.train.profile_steps,
            log=self.log.log,
        )
        sentinel = self._make_sentinel("rl")
        last_val = None
        run = {"first_step": True}
        rl_setup.end()
        try:
            with PreemptionHandler() as pre:
                while self.rl_epochs < target:
                    try:
                        last_val = self._rl_epoch(
                            scst, rl_batcher, meter, profiler, sentinel, pre,
                            run, epochs_left=target - self.rl_epochs,
                        )
                    except RollbackRequested as e:
                        scst.drop_primed()
                        self._apply_rollback("rl", e, sentinel)
                    except PeerLost as e:
                        if self.cfg.train.elastic != "degraded":
                            raise
                        scst.drop_primed()
                        self._continue_degraded("rl", e)
                        # the decode/update closures and the batcher's host
                        # share are mesh-shaped: rebuild on the shrunk mesh
                        scst, rl_batcher = build_scst()
                        self._rl_batcher = rl_batcher
                        run["first_step"] = True
                    except health_mod.HostRejoin as e:
                        scst.drop_primed()
                        if self._continue_regrown("rl", e):
                            # rebuild mesh-shaped closures on the FULL mesh
                            scst, rl_batcher = build_scst()
                            self._rl_batcher = rl_batcher
                            run["first_step"] = True
        finally:
            self._rl_batcher = None
            scst.drop_primed()
            self._close_feed()      # as in train_xe
        return last_val

    def _rl_epoch(self, scst, rl_batcher, meter, profiler, sentinel, pre,
                  run, epochs_left: int = 1) -> float | None:
        """One RL epoch (possibly a resumed remainder); ``epochs_left`` as
        :meth:`_xe_epoch`'s."""
        cfg = self.cfg
        log_every = cfg.train.log_every_steps
        # keyed off the global epoch so a resumed RL phase replays the same
        # per-epoch batch order as an uninterrupted run (pinned per epoch so
        # a rollback replay re-keys identically)
        rl_batcher.epoch_index = self.epoch
        skip = self._resume_rl_batch
        self._resume_rl_batch = 0
        # drain-aware seam replay: the tokens the drained pipeline decoded
        # for exactly this (epoch, batch) position — replayed so the seam
        # batch is not re-decoded against params one update fresher than
        # the uninterrupted schedule. They are the call's first decode: the
        # batch the epoch (re)starts at or, where every batch of this epoch
        # was applied before the save, the next epoch's first (decoded while
        # priming). Anything else (position mismatch, strict pipeline off)
        # falls back to the old re-decode.
        seam = None
        decoupled = cfg.train.rl_topology == "decoupled"
        seam_capable = cfg.rl.pipelined or decoupled
        if self._pending_seam is not None:
            cand, self._pending_seam = self._pending_seam, None
            at = (cand["epoch"], cand["batch_index"])
            if seam_capable and at in (
                (self.epoch, skip), (self.epoch + 1, 0)
            ):
                seam = cand
            else:
                self.log.log(
                    "seam_discarded", epoch=self.epoch, batch_index=skip,
                    seam_epoch=cand["epoch"],
                    seam_batch_index=cand["batch_index"],
                )
        if scst.primed is not None:
            # the epoch before this one opened it inside its drain: its
            # first two batches are decoded, its key is folded and split
            batches, ep_rng = scst.primed.batches, scst.primed.rng
        else:
            with obs.span("rl.epoch.keys"):
                ep_rng = self._rl_epoch_key(self.epoch, skip)
            batches = self._rl_device_batches(rl_batcher, skip=skip,
                                              epochs=epochs_left)
        kw = {}
        if cfg.rl.pipelined and not decoupled and epochs_left > 1:

            def next_epoch():
                """The epoch after this one, for the pipeline to run on
                into: the batches the feed's worker staged ahead, and its
                key."""
                rl_batcher.epoch_index = self.epoch + 1
                with obs.span("rl.epoch.keys"):
                    key = self._rl_epoch_key(self.epoch + 1)
                return self._rl_device_batches(
                    rl_batcher, epochs=epochs_left - 1
                ), key

            kw["next_epoch"] = next_epoch
        step_counter = {"step": int(self.state.step)}
        batch_counter = {"n": skip}
        if obs.enabled():
            obs.set_context(phase="rl", epoch=self.epoch + 1)
        meter.begin_epoch()
        rewards = []
        valid_rows = []

        def on_step(m):
            rewards.append(m["reward_mean"])
            valid_rows.append(m["valid_rows"])
            step_counter["step"] += 1
            batch_counter["n"] += 1
            # record before push (see _xe_epoch): the dict mixes device
            # scalars (rl_loss, grad_norm, upd_ratio/*) with host floats
            # (reward_*, advantage_*, sample_entropy) — the recorder's
            # batched device_get handles both
            flight.record(step_counter["step"], "rl", m)
            sentinel.push(
                step_counter["step"], m["rl_loss"], m.get("nonfinite")
            )
            if obs.enabled():
                obs.set_context(step=step_counter["step"])
            if log_every and step_counter["step"] % log_every == 0:
                self.log.log(
                    "rl_step",
                    phase="rl",
                    step=step_counter["step"],
                    epoch=self.epoch + 1,
                    reward=float(m["reward_mean"]),
                    rl_loss=float(m["rl_loss"]),
                    grad_norm=float(m["grad_norm"]),
                )
                flight.flush()
            obs.maybe_snapshot(step_counter["step"])
            profiler.tick()
            meter.tick(cfg.data.batch_size, first=run["first_step"])
            run["first_step"] = False
            chaos.visit("rl.step")
            if self.health is not None:
                self.health.note_step(step_counter["step"])
            self._poll_rejoin()

        # pipelined epoch (rl.pipelined, default): host reward for batch i
        # overlaps device update i-1 + decode i+1; batches are prefetched
        # to device by a host thread. pipelined=False: strict on-policy.
        # should_stop: a SIGTERM stops consuming at the next batch boundary
        # and the pipeline drains, so state == batch_counter steps exactly
        # the rl.epoch span's self time is what no span inside it claims
        # (rl.decode/reward/update, prefetch.wait, rl.epoch.drain): rng
        # splits, step bookkeeping, the epoch's unattributed remainder
        # drain-aware stop: the pipelined loop decodes the seam batch at
        # its exact schedule position and captures the tokens here; the
        # preemption/peer-loss save persists them next to the state
        seam_sink: dict = {}
        with obs.span("rl.epoch"):
            try:
                self.state, _ = scst.train_epoch(
                    self.state,
                    batches,
                    ep_rng,
                    on_step=on_step,
                    pipelined=cfg.rl.pipelined,
                    should_stop=lambda: pre.requested or (
                        self.health is not None and self.health.peer_lost
                    ) or self._regrow_host is not None,
                    seam=seam,
                    seam_sink=seam_sink if seam_capable else None,
                    **kw,
                )
            finally:
                batches.close()     # as in _xe_epoch
            if scst.primed is not None:
                # the epoch's last update is queued and a decode behind it:
                # the state's read-back runs under that decode. Begun here
                # and not inside the call: self.state has just let go of the
                # state before this one, whose cached host copy (174 MB in
                # the benchmark's cell) is freed before this one's is made;
                # with both alive in turn every other epoch was 20 ms longer
                # on four chips (PERF.md section 6, PR 47)
                host_copy_begin(self.state)
            profiler.stop()
            peer_lost = self.health is not None and self.health.peer_lost
            stop_seam = None
            if pre.requested or peer_lost or self._regrow_host is not None:
                # the tokens a stop's checkpoint carries: the batch the
                # pipeline was stopped at or, where it had already primed
                # the next epoch, that epoch's first
                stop_seam = seam_sink or scst.primed_seam()
            if pre.requested:
                self._preempt_save(
                    "rl", step_counter["step"], batch_counter["n"], sentinel,
                    seam=stop_seam,
                )
            if peer_lost:
                self._peer_loss_save(
                    "rl", step_counter["step"], batch_counter["n"], sentinel,
                    seam=stop_seam,
                )
            if self._regrow_host is not None:
                self._regrow_save(
                    "rl", step_counter["step"], batch_counter["n"], sentinel,
                    seam=stop_seam,
                )
            # the wait for the queued updates: both read device scalars of
            # the epoch's last steps back (the device is busy meanwhile)
            with obs.span("rl.epoch.drain"):
                flight.flush()
                sentinel.flush()
                scst.observe_update_positions()
        self.epoch += 1
        self.rl_epochs += 1
        n_valid = float(np.sum(valid_rows)) if valid_rows else 0.0
        self.log.log(
            "rl_epoch",
            epoch=self.epoch,
            # per-step rewards are scored on this host's rows only; weight
            # by valid rows (wrap-padded final batches have fewer) and
            # reduce exactly across processes
            reward=multihost.global_weighted_mean(
                # host floats from the reward computer — no device sync
                float(np.dot(rewards, valid_rows)) if valid_rows else 0.0,
                n_valid,
            ),
            **meter.epoch_summary(),
        )
        obs.snapshot_metrics(epoch=self.epoch)
        # after a primed epoch the checkpoint carries the tokens of the next
        # epoch's first batch: it was decoded one update before this state
        return self._validate_and_checkpoint(
            step_counter["step"],
            seam=scst.primed_seam if scst.primed is not None else None,
        )

    def _rl_epoch_key(self, epoch: int, skip: int = 0):
        """The sampling key of RL epoch ``epoch``, advanced past ``skip``
        batches. FOLDED from the global epoch, not drawn from a running
        split chain, so a resumed phase continues the stream (epoch k uses
        fold_in(base, k) whether or not the process restarted); a rollback
        salt re-randomizes it together with the batch order. The seed is
        static (device_key: one value a process, a jit-cache hit every
        epoch); the salt and the epoch are TRACED arguments of one fold-in
        program for all epochs (device_fold_in), uploaded by an explicit
        device_put: static, each epoch would compile a program of its own
        right here; eager, each would stage its integer through an implicit
        transfer inside the sanitized loop."""
        rng = device_key(self.cfg.train.seed + 1)
        if self.batcher.salt:
            rng = device_fold_in(rng, self.batcher.salt)
        rng = device_fold_in(rng, epoch)
        # mid-epoch resume: advance the per-batch split chain past the
        # ``skip`` batches the checkpoint already trained on
        for _ in range(skip):
            rng = jax.random.split(rng)[0]
        return rng

    # ---- validation --------------------------------------------------------

    def _validate_and_checkpoint(self, step_no: int | None = None,
                                 seam=None) -> float | None:
        """Validation where it is due, then the epoch-end checkpoint.
        ``seam`` (a primed RL epoch's end: a callable that gives the next
        epoch's first batch's tokens) rides along as ``seam.npz``, as a
        seam does beside a step save; it is asked for and serialised only
        where a checkpoint is really written, after the read-back."""
        value = None
        if self.validator is not None and (
            self.epoch % self.cfg.train.eval_every_epochs == 0
        ):
            # multi-host: validation runs on EVERY process (the sharded
            # decode is a collective program), but only process 0 writes the
            # checkpoint on the shared filesystem below
            result = self.validator.evaluate(self.state.params)
            value = result["metrics"].get("CIDEr-D")
            self.log.log("validate", epoch=self.epoch, cider_d=value)
        if seam is not None and multihost.is_multiprocess():
            # the tokens are gathered across processes: every process takes
            # part, before process 0 goes on alone
            tokens = seam()
            seam = lambda: tokens   # noqa: E731
        if jax.process_index() != 0:
            return value
        with obs.span("ckpt", kind="epoch"):
            # full config snapshot: the reference's `infos` pickle carried
            # the whole opt namespace (SURVEY.md §5 checkpoint row);
            # global_step/phase/batch_index/data_salt feed mid-epoch
            # resume ordering. Made before the read-back: after a primed RL
            # epoch the state's copy is in flight, and this runs under it
            infos = self._ckpt_infos(step_no=step_no)
            if seam is not None:
                infos["extra_files"] = {
                    "seam.npz": lambda: self._seam_bytes(
                        seam(), self.epoch, 0
                    ),
                }
            # the read-back apart from the write: it waits for whatever the
            # device still has queued, then the device idles for the copy
            # (after a primed RL epoch the copy was begun when the call
            # returned, behind the epoch's last update, and runs under the
            # next epoch's second decode)
            with obs.span("ckpt.readback"):
                host_state = host_copy(self.state)
            is_best = self.ckpt.save(host_state, value, infos=infos)
        if is_best:
            self.log.log("new_best", epoch=self.epoch, cider_d=value)
        return value
