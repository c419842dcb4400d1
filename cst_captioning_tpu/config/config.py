"""Dataclass configs for model / data / training / RL / eval / mesh.

Design notes (TPU-first):

- Everything that reaches a jitted function is static and hashable, so configs
  are frozen dataclasses — they can be closed over by ``jax.jit`` without
  retracing hazards.
- Token id conventions are fixed framework-wide: PAD=0, BOS=1, EOS=2, UNK=3.
  PAD=0 lets masks be computed as ``labels != 0`` on device, and keeps padded
  positions out of every loss/metric without extra bookkeeping.
- ``modalities`` is an ordered mapping name -> raw feature dim (e.g.
  ``{"resnet": 2048, "c3d": 500}``), mirroring the reference's multi-h5
  feature list but with the dims carried in config so model init needs no
  data peek.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass, field
from typing import Any, Mapping

PAD_ID = 0
BOS_ID = 1
EOS_ID = 2
UNK_ID = 3
NUM_SPECIAL_TOKENS = 4


def _freeze_modalities(m: Mapping[str, int]) -> tuple[tuple[str, int], ...]:
    return tuple((str(k), int(v)) for k, v in m.items())


# the decoder kinds ``ModelConfig.decoder`` names (models/captioner.py builds
# each); every kind but the first is a language-model stack behind a video
# prefix that runs the evaluation path only
DECODERS = ("lstm", "latent_moe", "sparse_linear", "eva", "window_moe",
            "cca_moe")


@dataclass(frozen=True)
class ModelConfig:
    """Caption model shape (reference ``model.py::CaptionModel`` capability)."""

    vocab_size: int = 512
    # ordered (name, raw_dim) pairs; tuple-of-tuples so the config is hashable.
    modalities: tuple[tuple[str, int], ...] = (("resnet", 2048),)
    d_embed: int = 512          # word embedding + per-modality frame embedding dim
    d_hidden: int = 512         # LSTM hidden size
    encoder: str = "meanpool"   # "meanpool" | "temporal_attention"
    d_att: int = 256            # additive-attention projection dim
    num_layers: int = 1         # LSTM layers (reference uses 1)
    dropout: float = 0.5
    max_len: int = 30           # max caption length incl. EOS
    max_frames: int = 60        # frame-axis padding length
    dtype: str = "bfloat16"     # compute dtype for MXU-friendly matmuls
    param_dtype: str = "float32"
    # sequence/context parallelism (SURVEY.md §5 long-context row): when set
    # to a mesh axis name, the model must run inside shard_map with the FRAME
    # axis of feats/masks sharded over that axis; the only frame-crossing
    # reductions (attention softmax, carry-init pooling) become collective
    # (pmax/psum over ICI), so videos longer than one chip's HBM still train
    # and decode. "" = single-device frame axis (the default).
    seq_axis: str = ""
    # decode-step implementation for the greedy/sampling/fused RL decode
    # loops (README "Decode fast path"): "xla" (the composite the loops'
    # lane-batched step compiles to, default) or "pallas"
    # (ops/decode_pallas.py — one fused kernel per step: attention + LSTM
    # stack + output projection with the decoder weights resident in VMEM
    # across the row grid). Decode is inference-only (REINFORCE gradients go
    # through the teacher-forced update path), so the kernel has no VJP;
    # parity-swept against the XLA step in tests/test_ops_decode_pallas.py;
    # on the chip not measured (no cell reaches it, PERF.md section 7)
    decode_impl: str = "xla"
    # fused RL decode stride: steps per driving-loop iteration (and per
    # pallas_call when decode_impl="pallas" — the multi-step kernel keeps
    # decoder weights VMEM-resident across the whole stride). 1 = the
    # per-step loop (the PR-4 behavior, kept as the exactness baseline).
    # Token/logprob-exact for every S by construction (pinned in
    # tests/test_decoding.py); larger strides coarsen the EOS early-exit
    # granularity, so S should stay well under the typical caption length
    decode_stride: int = 8
    # finished-lane compaction between strides: gather batch columns that
    # still have an unfinished lane into a dense prefix so the stride kernel
    # skips whole blocks of finished rows (XLA steps full width — the
    # compute win is the kernel's; the permutation round-trip is
    # token-exact either way). No-op at decode_stride=1 — compaction only
    # pays between strides. Off = step every row until the global exit
    decode_compact: bool = True
    # decoder kind: "lstm" (the attention-LSTM cell above, every field so
    # far) or "latent_moe" (models/latent_moe.py: a pre-norm residual stack
    # over a video prefix — latent attention (MLA) with a compressed cache,
    # one leading dense layer, then sigmoid-routed expert layers of which
    # this chip holds a share). The fields below are that stack's sizes
    # under the key names of the published config.json they are read from
    # (benchmark/configs/kimi_k2_ep32.json); the LSTM path reads none
    decoder: str = "lstm"
    hidden_size: int = 0
    num_hidden_layers: int = 0          # dense + expert layers held here
    first_k_dense_replace: int = 1      # leading layers with a dense FFN
    intermediate_size: int = 0          # the dense FFN's width
    moe_intermediate_size: int = 0      # one expert's width
    n_routed_experts: int = 0           # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 0
    routed_scaling_factor: float = 1.0
    num_attention_heads: int = 0
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # YaRN: (key, value) pairs of the published ``rope_scaling`` group
    # (factor, original_max_position_embeddings, beta_fast, beta_slow,
    # mscale, mscale_all_dim); pairs so that the config stays hashable
    rope_scaling: tuple[tuple[str, float], ...] = ()
    initializer_range: float = 0.02
    # the chip's share of each expert layer (expert parallelism): it holds
    # ``experts_held`` consecutive routed experts starting at
    # ``expert_share_index * experts_held``, routes over all
    # ``n_routed_experts`` and computes its own experts' part of the result
    experts_held: int = 0
    expert_share_index: int = 0
    # decoder kind "sparse_linear" (models/sparse_linear.py: a pre-norm
    # residual stack over a video prefix whose layers mix tokens one of two
    # ways, ``mixer_types`` says which: "minicpm4" is grouped-query softmax
    # attention over the key blocks each query selects, "lightning-attn"
    # causal linear attention with a per-head decay). Sizes under the key
    # names of the published config.json (benchmark/configs/
    # minicpm_sala_8l.json); it also reads hidden_size, num_hidden_layers,
    # intermediate_size, num_attention_heads, rms_norm_eps, rope_theta and
    # initializer_range above
    mixer_types: tuple[str, ...] = ()
    num_key_value_heads: int = 0
    head_dim: int = 0
    lightning_nh: int = 0
    lightning_head_dim: int = 0
    # muP: token embeddings times ``scale_emb``, every residual branch times
    # ``scale_depth / sqrt(published_layers)``, logits over ``hidden_size /
    # dim_model_base``. ``published_layers`` is the depth of the model the
    # held layers are cut from, ``first_layer_index`` where in it they start
    # (the linear layers' decay slopes scale with the published depth)
    scale_emb: float = 1.0
    scale_depth: float = 1.0
    dim_model_base: int = 0
    published_layers: int = 0
    first_layer_index: int = 0
    # the sparse layers' selection (MiniCPM4's ``sparse_config``)
    sparse_kernel_size: int = 32
    sparse_kernel_stride: int = 16
    sparse_block_size: int = 64
    sparse_topk: int = 64
    sparse_window_size: int = 2048
    sparse_init_blocks: int = 1
    sparse_dense_len: int = 8192
    # decoder kind "eva" (models/eva.py: a pre-norm residual stack over a
    # video prefix whose every layer mixes tokens by EVA attention: exact
    # keys inside a window of ``window_size`` positions, one learned summary
    # for every ``chunk_size`` positions of the windows before it, one
    # softmax over both; float32 residual stream, norms with a unit offset,
    # an output head of ``num_pred_heads`` blocks of ``vocab_size`` columns
    # of which plain decoding reads the first). Sizes under the key names of
    # the published config.json (benchmark/configs/evabyte_8l.json); it also
    # reads hidden_size, num_hidden_layers, intermediate_size,
    # num_attention_heads, rms_norm_eps and rope_theta above
    window_size: int = 2048
    chunk_size: int = 16
    num_pred_heads: int = 1
    init_std: float = 0.02
    # decoder kind "window_moe" (models/window_moe.py: a pre-norm residual
    # stack over a video prefix whose layers attend one of two ways,
    # ``mixer_types`` says which: "full" is causal grouped-query softmax
    # attention with ``num_key_value_heads`` key/value heads and rope at
    # ``rope_theta``; "window" sees the last ``sliding_window`` positions,
    # its own included, with ``swa_num_key_value_heads`` key/value heads,
    # rope at ``swa_rope_theta`` and one learned sink a query head in the
    # softmax's denominator. Keys of ``head_dim``, values of ``v_head_dim``
    # scaled by ``attention_value_scale``, rope on the first ``int(head_dim *
    # partial_rotary_factor)`` dimensions. The FFN is latent_moe's: dense in
    # the layers whose published index (``first_layer_index`` + the held
    # index) is under ``first_k_dense_replace``, this chip's share of the
    # routed experts in the others (``n_shared_experts`` 0: no shared branch).
    # Sizes under the key names of the published config.json
    # (benchmark/configs/mimo_v2_5_ep16.json); it also reads hidden_size,
    # num_hidden_layers, intermediate_size, num_attention_heads, rms_norm_eps,
    # initializer_range and the expert fields above
    swa_num_key_value_heads: int = 0
    sliding_window: int = 128
    partial_rotary_factor: float = 1.0
    swa_rope_theta: float = 10000.0
    attention_value_scale: float = 1.0
    # decoder kind "cca_moe" (models/cca_moe.py: a pre-norm residual stack
    # over a video prefix whose every layer attends in a compressed latent,
    # ``num_attention_heads`` query heads over ``num_key_value_heads``
    # key/value heads of ``head_dim``, q and k mixed along the sequence by two
    # causal convolutions of widths ``cca_time0`` (depthwise) and
    # ``cca_time1`` (a head a group) before the scores, half of a value taken
    # from the token before; its FFN is one of ``n_routed_experts`` experts of
    # ``moe_intermediate_size``, or none, chosen top-1 by an MLP router of
    # width ``router_hidden_size`` whose input is carried from layer to
    # layer; learned scales on the residual merge; the head is the token
    # embedding, ``tie_word_embeddings``). Sizes under the key names of the
    # published config.json (benchmark/configs/zaya1_8b_20l.json); it also
    # reads hidden_size, num_hidden_layers, partial_rotary_factor, rope_theta,
    # rms_norm_eps, initializer_range and the expert-share fields above
    cca_time0: int = 2
    cca_time1: int = 2
    router_hidden_size: int = 0
    tie_word_embeddings: bool = False

    def __post_init__(self):
        if self.decoder not in DECODERS:
            raise ValueError(
                f"unknown decoder: {self.decoder!r} (expected one of "
                f"{', '.join(repr(d) for d in DECODERS)})"
            )
        object.__setattr__(self, "mixer_types",
                           tuple(str(m) for m in self.mixer_types))
        object.__setattr__(
            self, "rope_scaling",
            tuple((str(k), v) for k, v in (
                self.rope_scaling.items()
                if isinstance(self.rope_scaling, Mapping)
                else self.rope_scaling)),
        )
        if isinstance(self.modalities, Mapping):
            object.__setattr__(self, "modalities", _freeze_modalities(self.modalities))
        else:
            object.__setattr__(
                self, "modalities", tuple((str(k), int(v)) for k, v in self.modalities)
            )
        if self.encoder not in ("meanpool", "temporal_attention"):
            raise ValueError(f"unknown encoder: {self.encoder!r}")
        if self.decode_impl not in ("xla", "pallas"):
            raise ValueError(
                f"unknown decode_impl: {self.decode_impl!r} "
                "(expected 'xla' or 'pallas')"
            )
        if self.decode_stride < 1:
            raise ValueError(
                f"decode_stride {self.decode_stride} must be >= 1"
            )
        if self.decode_impl == "pallas" and self.seq_axis:
            # the kernel's in-VMEM softmax is single-device; a frame-sharded
            # memory bank needs the collective softmax path
            raise ValueError(
                "decode_impl='pallas' cannot run with a frame-sharded "
                "memory bank (seq_axis set) — the kernel's attention "
                "softmax is not collective"
            )

    @property
    def modality_names(self) -> tuple[str, ...]:
        return tuple(name for name, _ in self.modalities)

    @property
    def modality_dims(self) -> dict[str, int]:
        return dict(self.modalities)


@dataclass(frozen=True)
class DataConfig:
    """Dataset wiring (reference ``dataloader.py`` capability)."""

    dataset: str = "synthetic"          # "msvd" | "msrvtt" | "synthetic"
    feature_files: tuple[tuple[str, str], ...] = ()  # (modality, h5 path)
    info_json: str = ""                 # vocab + splits + tokenized captions
    consensus_weights: str = ""         # WXE per-caption weights (npz), optional
    cider_df: str = ""                  # precomputed CIDEr-D document freqs, optional
    batch_size: int = 64                # global batch (split across data axis)
    seq_per_vid: int = 1                # caption rows sampled per video (XE)
    shuffle_seed: int = 0
    # device prefetch depth: batches staged on the device ahead of the step.
    # It also sizes the host staging ring the training loops collate into
    # (prefetch + 2 slots of one batch each, reused; data/prefetch.py)
    prefetch: int = 2
    # keep every video's (padded) features in host RAM after the first h5
    # read: repeat epochs skip h5py entirely, and a batch is one gather a
    # stream. What it holds: one contiguous f32 table a stream, indexed by
    # record, bytes = n_videos x max_frames x sum(dims) x 4 (full MSR-VTT
    # ResNet+C3D at 28 frames: 1.86 GB), touched as rows are filled. Opt-in:
    # size it to the host. Cached features come back READ-ONLY (in-place
    # mutation raises instead of silently poisoning later epochs); the
    # uncached path returns fresh writable arrays — consumers that mutate
    # features must copy first
    cache_features: bool = False

    def __post_init__(self):
        if isinstance(self.feature_files, Mapping):
            object.__setattr__(
                self,
                "feature_files",
                tuple((str(k), str(v)) for k, v in self.feature_files.items()),
            )


@dataclass(frozen=True)
class TrainConfig:
    """Optimization loop (reference ``train.py`` capability)."""

    optimizer: str = "adam"
    lr: float = 1e-4
    lr_decay: float = 0.5               # multiplicative decay factor
    lr_decay_every: int = 3             # epochs between decays (0 = constant)
    grad_clip: float = 5.0              # global-norm clip
    epochs: int = 30
    seed: int = 1234
    weight_decay: float = 0.0
    label_smoothing: float = 0.0
    loss: str = "xe"                    # "xe" | "wxe"

    def __post_init__(self):
        if self.on_divergence not in ("off", "skip_batch", "rollback", "abort"):
            raise ValueError(
                f"unknown on_divergence policy {self.on_divergence!r} "
                "(expected 'off', 'skip_batch', 'rollback', or 'abort')"
            )
        if self.ckpt_every_steps < 0 or self.keep_ckpts < 1 or self.max_rollbacks < 0:
            raise ValueError(
                "resilience knobs out of range: ckpt_every_steps >= 0, "
                "keep_ckpts >= 1, max_rollbacks >= 0 required "
                f"(got {self.ckpt_every_steps}, {self.keep_ckpts}, "
                f"{self.max_rollbacks})"
            )
        if self.elastic not in ("strict", "degraded"):
            raise ValueError(
                f"unknown elastic mode {self.elastic!r} "
                "(expected 'strict' or 'degraded')"
            )
        if self.health_interval_s <= 0 or self.peer_timeout_s <= 0:
            raise ValueError(
                "health knobs out of range: health_interval_s > 0 and "
                f"peer_timeout_s > 0 required (got {self.health_interval_s}, "
                f"{self.peer_timeout_s})"
            )
        if self.health_sim_hosts < 0:
            raise ValueError(
                f"health_sim_hosts {self.health_sim_hosts} must be >= 0 "
                "(0 = the real process count)"
            )
        if self.comm_dtype not in ("f32", "bf16"):
            raise ValueError(
                f"unknown comm_dtype {self.comm_dtype!r} "
                "(expected 'f32' or 'bf16')"
            )
        if self.comm_bucket_mb < 0:
            raise ValueError(
                f"comm_bucket_mb {self.comm_bucket_mb} must be >= 0 "
                "(0 = one message per leaf)"
            )
        if self.recorder_steps < 0:
            raise ValueError(
                f"recorder_steps {self.recorder_steps} must be >= 0 "
                "(0 = flight recorder off)"
            )
        if self.spike_mode not in ("fixed", "adaptive"):
            raise ValueError(
                f"unknown spike_mode {self.spike_mode!r} "
                "(expected 'fixed' or 'adaptive')"
            )
        if self.spike_mode == "adaptive":
            if self.spike_factor <= 0:
                raise ValueError(
                    "spike_mode='adaptive' needs spike_factor > 0 — the "
                    "factor is the adaptive bound's ceiling clamp"
                )
            if not 0 < self.spike_factor_min <= self.spike_factor:
                raise ValueError(
                    f"spike_factor_min {self.spike_factor_min} must be in "
                    f"(0, spike_factor={self.spike_factor}]"
                )
        if self.rl_topology not in ("sync", "decoupled"):
            raise ValueError(
                f"unknown rl_topology {self.rl_topology!r} "
                "(expected 'sync' or 'decoupled')"
            )
    # per-step JSONL events (loss/reward + grad_norm every N steps; 0 = off,
    # keeping logs to per-epoch summaries)
    log_every_steps: int = 0
    eval_every_epochs: int = 1
    ckpt_dir: str = "checkpoints"
    resume: str = ""                    # "", "auto", or explicit ckpt path
    # observability (SURVEY.md §5 rows 1-2; obs/ package)
    profile_dir: str = ""               # jax.profiler trace output dir ("" = off)
    profile_steps: int = 10             # steps to trace (after the compile step)
    debug_nans: bool = False            # jax_debug_nans sanitizer mode
    # unified obs subsystem (spans + metrics + run report, README
    # "Observability"): off by default — every span/counter call in the hot
    # paths degrades to a no-op. Snapshot cadence rides log_every_steps.
    obs: bool = False
    obs_dir: str = ""                   # run dir ("" = <ckpt_dir>/obs)
    # ---- resilience (resilience/ package; README "Preemption-safe training")
    # mid-epoch step_<n> checkpoint interval, in steps (0 = epoch-end saves
    # only; SIGTERM-triggered saves happen regardless)
    ckpt_every_steps: int = 0
    keep_ckpts: int = 3                 # keep-last-K rotation for step_* ckpts
    # divergence sentinel policy: "off" | "skip_batch" (on-device guard
    # excludes the non-finite update, run continues) | "rollback" (restore
    # last-good checkpoint, re-randomize data order) | "abort"
    on_divergence: str = "skip_batch"
    # loss-spike sentinel: flag a finite loss > factor * median(recent
    # window); 0 = NaN/inf detection only
    spike_factor: float = 0.0
    # "fixed" = the factor-of-median bound above, untouched. "adaptive" =
    # the anomaly detector's EWMA moments set the bound (mean + z*std,
    # clamped to [spike_factor_min, spike_factor] x median — never looser
    # than fixed; catches slow ramps the fixed factor misses). Requires
    # spike_factor > 0; shares the detector's loss Ewma when `anomaly` is on
    spike_mode: str = "fixed"
    spike_factor_min: float = 1.5       # adaptive bound's floor clamp
    max_rollbacks: int = 2              # rollback budget per run before aborting
    # ---- elastic multi-host resilience (resilience/health.py; README
    # "Elastic training"): off by default — the hot loops then carry zero
    # extra work (the peer-loss poll is gated on `health`)
    health: bool = False                # run the heartbeat/watchdog monitor
    health_dir: str = ""                # heartbeat dir ("" = <ckpt_dir>/health)
    health_interval_s: float = 0.5      # watchdog beat/poll cadence
    peer_timeout_s: float = 5.0         # heartbeat staleness before a strike
    # consecutive stale polls (the debounce) before a peer is declared lost
    health_misses: int = 2
    # chaos/test only: pretend the cluster has N hosts (this process is host
    # 0, the phantoms die only via the partial_preempt fault); 0 = the real
    # jax.process_count()
    health_sim_hosts: int = 0
    # on peer loss after the drain+save: "strict" aborts (raise PeerLost;
    # the restarted full-mesh run resumes bit-exactly) | "degraded"
    # rendezvous the survivors, rebuild a shrunk data mesh, reshard from the
    # drained checkpoint, and continue with per-host batch rescaling
    elastic: str = "strict"
    # grow-back direction (only meaningful with elastic="degraded"): a
    # degraded run polls for generation-stamped rejoin markers at batch
    # boundaries and re-admits a validated recovered host — drain, full-mesh
    # rendezvous, reshard state from the SURVIVORS (never the rejoiner's
    # stale checkpoint), continue the epoch remainder. False = a degraded
    # run stays degraded (the pre-regrow ratchet-down behavior)
    elastic_regrow: bool = True
    # a cross-host collective slower than this emits a dcn_stall event +
    # counter (the DCN-stall span around the multihost barrier/broadcast)
    dcn_stall_s: float = 2.0
    # ---- gradient communication (parallel/comms.py; README "Gradient
    # communication"): how the data-parallel factories allreduce grads.
    # Target payload per collective in MiB — the grad tree coalesces into
    # family-ordered contiguous buckets of at most this many WIRE bytes and
    # one psum runs per bucket (0 = one psum per leaf). Bit-identical to the
    # per-leaf spelling at f32 — psum is elementwise
    comm_bucket_mb: float = 4.0
    # "f32" (bit-exact default) | "bf16": grads ride the wire in bfloat16,
    # halving bytes; params/optimizer moments stay f32 (master accumulation)
    comm_dtype: str = "f32"
    # overlap the grad reduction with the backward scan: each rl.update_chunks
    # chunk's psum starts while the next chunk's backward runs (double-
    # buffered carry). Needs rl.update_chunks >= 2; trades (chunks+1)x wire
    # bytes for latency hiding — see the README section before enabling
    comm_overlap: bool = False
    # ---- flight recorder + anomaly detection (obs/recorder.py, obs/anomaly.py;
    # README "Observability"): ring capacity in steps for the black-box
    # per-step record buffer (0 = off; requires `obs`). On divergence/
    # rollback/chaos/preemption the ring dumps as a postmortem bundle under
    # the obs dir, rendered by `cli.obs_report --postmortem <bundle>`
    recorder_steps: int = 0
    # online EWMA z-score + stall detection over the recorder's loss/
    # grad-norm/reward/step-time streams; verdicts land inline in the ring
    # records and as `anomaly` events + obs.anomaly.<kind> counters
    anomaly: bool = False
    # ---- RL actor/learner topology (rl/async_scst.py; README "Decoupled
    # actor/learner RL"): "sync" (default) = today's synchronous loop,
    # bit-identical to the pre-topology trainer. "decoupled" = the data mesh
    # splits into actor and learner submeshes (rl.actor_fraction) — actors
    # run the fused decode continuously into a device-resident rollout ring
    # (rl.rollout_depth), learners consume it with the existing rl_update
    # factories, and params broadcast actor-ward on the rl.staleness_bound
    # schedule. Decoupled with depth 1 / bound 0 / actor = full mesh is the
    # strict replay mode, pinned bit-identical to "sync"
    rl_topology: str = "sync"


@dataclass(frozen=True)
class RLConfig:
    """CST / self-critical phase (reference RL loop, SURVEY.md §3.2)."""

    enabled: bool = False
    num_rollouts: int = 5               # K Monte-Carlo samples per clip
    baseline: str = "greedy"            # "greedy" (SCST) | "scb" (self-consensus) | "none"
    reward_cider_weight: float = 1.0
    reward_bleu4_weight: float = 0.0
    temperature: float = 1.0
    lr: float = 2e-5                    # RL phase LR (fresh optimizer on handoff)
    epochs: int = 20
    init_from: str = ""                 # XE checkpoint to start from
    # True (default): the two-stage pipelined epoch — per iteration the
    # dispatch order is update(i-2) -> decode(i) -> host-score(i-1), so a
    # full device step stays queued while the host computes the consensus
    # reward and the device never idles on it. The decoded policy is one
    # update stale (identical to a plain decode-then-score loop — update
    # i-1 cannot be ready before decode i without blocking). False: strict
    # on-policy SCST, decode -> score -> update serialized per batch,
    # exactly the reference's loop (SURVEY.md §3.2); measured reward-curve
    # delta between the modes is recorded in BASELINE.md
    pipelined: bool = True
    # host threads for the native consensus-reward scorer; 0 = all cores
    # (os.cpu_count()). The reward is the host hot path the pipeline hides —
    # size it to the machine, not a hardcoded cap
    reward_threads: int = 0
    # scale applied to sentence-BLEU4 (in [0,1]) before mixing with CIDEr-D
    # (x10 scale) in the consensus reward: reward = w_c*CIDErD +
    # w_b*BLEU4*scale. Default 10.0 puts both terms on a like scale —
    # UNVERIFIED interpretation of the reference's convention (BASELINE.md
    # "Mixed-reward BLEU4 scale"); exposed so it can be matched when the
    # reference becomes readable
    reward_bleu4_scale: float = 10.0
    # gradient accumulation over the K rollout axis in the REINFORCE update:
    # the update teacher-forces K*B sequences at once, which caps the batch
    # size under HBM; update_chunks=C (dividing K) re-runs forward+backward
    # on K/C rollouts at a time — the same total gradient up to float
    # summation order, NOT bit-equal to the fused path (1 = fused). It
    # divides K, never the batch: a chunk is K/C rollouts of EVERY row a
    # device holds. The rows are cut too, but not from here: with C > 1 the
    # update runs in equal row blocks chosen from the traced shape
    # (rl/scst.py::_chunked_loss_grads, _ROW_BLOCK_CAP), no field for it
    update_chunks: int = 1
    # ---- decoupled actor/learner knobs (train.rl_topology="decoupled";
    # rl/async_scst.py, README "Decoupled actor/learner RL") ----
    # device-resident rollout ring depth in batches: actors decode up to
    # this many batches ahead of the learner (2 = the double buffer).
    # Depth 1 serializes actor and learner — with staleness_bound 0 and a
    # full-mesh actor that is the strict schedule replaying "sync" bit-for-bit
    rollout_depth: int = 2
    # max learner updates a rollout's params may lag at consumption time; a
    # staler rollout is dropped and re-decoded (recounted) under the actor's
    # current params with the entry's stored RNG key, so the drop/recount
    # sequence is deterministic run-to-run
    staleness_bound: int = 1
    # fraction of the data-axis devices handed to the actor submesh (the
    # remainder learn); both sides are clamped to >= 1 device, and a 1-device
    # mesh (or mesh=None) runs both roles on the same device
    actor_fraction: float = 0.5
    # ---- online serving-as-actor knobs (rl/online.py; README "Online RL
    # from served traffic") ----
    # completed served requests buffered per learner batch before the
    # batch enters the rollout ring (the online analogue of
    # data.batch_size; a trailing partial buffer waits for more traffic)
    online_batch_size: int = 4
    # learner updates between param publishes into the live CaptionService
    # (1 = publish after every update). The publish is version-stamped with
    # the learner's update counter and applies at the service's next stride
    # boundary — drain-free, with in-flight requests pinned to their
    # admission version
    swap_every: int = 1


@dataclass(frozen=True)
class EvalConfig:
    """Evaluation (reference ``test.py`` capability)."""

    beam_size: int = 5
    max_len: int = 30
    min_len: int = 0              # suppress EOS for the first N steps (0 = off)
    length_penalty: float = 0.0         # 0 = pure sum-logprob (reference behavior)
    split: str = "test"
    # selector names understood by metrics.scorer.CaptionScorer
    metrics: tuple[str, ...] = ("Bleu", "ROUGE_L", "METEOR_approx", "CIDEr", "CIDEr-D")
    results_json: str = ""
    # "lanes" = beam-on-decode-lanes fast path, "reference" = the sequential
    # bit-parity oracle (decoding/beam.py; token- and score-bit-exact pair)
    beam_impl: str = "lanes"
    # NPAD anytime mode (arXiv 1605.03835): >0 decodes greedy + this many
    # noise-perturbed lanes and answers with the best-sum-logprob lane
    # INSTEAD of beam search — the latency-budget eval answer (0 = off)
    npad_lanes: int = 0
    npad_temperature: float = 1.0
    npad_seed: int = 0
    # two-stage eval pipeline: device decodes batch i+1 while a worker pool
    # tokenizes batch i's captions on the host; metric tables stay
    # bit-identical to the serial path (eval/evaluator.py)
    pipelined: bool = True
    score_workers: int = 4        # tokenizer threads feeding the drain
    # run the encoder pass (for a language-model decoder the prefill of the
    # video prefix) as a compiled program of its own, ``eval_prefill``, and
    # the beam search from its output as a second: a device trace then tells
    # the prefix from the caption's steps by the programs' names. Beam
    # search on one device only (eval/evaluator.py)
    prefill_program: bool = False

    def __post_init__(self):
        if self.beam_impl not in ("lanes", "reference"):
            raise ValueError(
                f"eval.beam_impl must be 'lanes' or 'reference', got "
                f"{self.beam_impl!r}"
            )
        if self.npad_lanes < 0:
            raise ValueError(
                f"eval.npad_lanes {self.npad_lanes} must be >= 0 (0 = off)"
            )
        if self.npad_lanes and self.npad_temperature <= 0:
            raise ValueError(
                f"eval.npad_temperature {self.npad_temperature} must be > 0"
            )
        if self.score_workers < 1:
            raise ValueError(
                f"eval.score_workers {self.score_workers} must be >= 1"
            )


@dataclass(frozen=True)
class MeshConfig:
    """Device mesh (replaces torch.nn.DataParallel / NCCL, SURVEY.md §2).

    Axis names are chosen so a future multi-host ('dcn', 'data') hierarchy can
    be layered in without changing call sites.
    """

    data_axis: str = "data"
    num_devices: int = 0                # 0 = all visible devices
    # >1: 2-D ('data','seq') mesh — the FRAME axis shards over 'seq' with the
    # collective attention softmax (long-context path, SURVEY.md §5); must
    # divide num_devices and model.max_frames
    seq_devices: int = 1
    # >1: 2-D ('data','mp') mesh — flagship-XL model parallelism: the vocab
    # head / embedding (and the training-side LSTM gates) shard over 'mp'
    # per train/mesh.MP_PARAM_PARTITION_RULES; must divide the device count
    # and model.vocab_size / model.d_hidden. Exclusive with seq_devices > 1.
    mp_devices: int = 1


@dataclass(frozen=True)
class ExperimentConfig:
    name: str = "experiment"
    model: ModelConfig = field(default_factory=ModelConfig)
    data: DataConfig = field(default_factory=DataConfig)
    train: TrainConfig = field(default_factory=TrainConfig)
    rl: RLConfig = field(default_factory=RLConfig)
    eval: EvalConfig = field(default_factory=EvalConfig)
    mesh: MeshConfig = field(default_factory=MeshConfig)

    def __post_init__(self):
        if self.model.decode_impl == "pallas" and self.mesh.seq_devices > 1:
            # the decode kernel fuses its own (single-device) attention
            # softmax — it cannot express the collective 'seq' softmax
            raise ValueError(
                "decode_impl='pallas' is not implemented for the "
                "sequence-parallel ('seq_devices > 1') path; use one or the "
                "other"
            )
        if self.rl.enabled and self.model.decoder != "lstm":
            # rl/scst.py's decode, update and FLOP ledger unroll the LSTM
            # cell from one encoder pass; fail here, not at the first step
            raise ValueError(
                f"rl.enabled needs model.decoder='lstm' (got "
                f"{self.model.decoder!r}): the SCST path has no "
                "teacher-forced update for another decoder kind yet"
            )
        if self.rl.enabled and (
            self.rl.update_chunks < 1
            or self.rl.num_rollouts % self.rl.update_chunks
        ):
            # catch at config time, not at the first RL step after a
            # potentially multi-hour XE phase
            raise ValueError(
                f"rl.update_chunks {self.rl.update_chunks} must be >= 1 and "
                f"divide rl.num_rollouts {self.rl.num_rollouts}"
            )
        if self.rl.reward_threads < 0:
            raise ValueError(
                f"rl.reward_threads {self.rl.reward_threads} must be >= 0 "
                "(0 = all cores)"
            )
        if self.train.comm_overlap and self.rl.update_chunks < 2:
            # overlap hides the psum behind the NEXT chunk's backward — with
            # one chunk there is nothing to hide behind
            raise ValueError(
                "train.comm_overlap requires rl.update_chunks >= 2 (the "
                f"chunk boundary is the overlap seam; got "
                f"{self.rl.update_chunks})"
            )
        if self.train.rl_topology == "decoupled":
            if self.rl.rollout_depth < 1:
                raise ValueError(
                    f"rl.rollout_depth {self.rl.rollout_depth} must be >= 1 "
                    "for train.rl_topology='decoupled'"
                )
            if self.rl.staleness_bound < 0:
                raise ValueError(
                    f"rl.staleness_bound {self.rl.staleness_bound} must be "
                    ">= 0 (0 = strict on-policy consumption)"
                )
            if not 0.0 < self.rl.actor_fraction < 1.0:
                raise ValueError(
                    f"rl.actor_fraction {self.rl.actor_fraction} must be in "
                    "(0, 1) — both submeshes need at least one device's share"
                )
            if self.mesh.seq_devices > 1:
                # the SP trainer's decode/update live inside one shard_map
                # over ('data','seq'); splitting 'data' under it needs a
                # submesh-aware SP story first
                raise ValueError(
                    "train.rl_topology='decoupled' is not implemented for "
                    "the sequence-parallel ('seq_devices > 1') path"
                )
        if self.rl.online_batch_size < 1:
            raise ValueError(
                f"rl.online_batch_size {self.rl.online_batch_size} must be "
                ">= 1 (served requests per online learner batch)"
            )
        if self.rl.swap_every < 1:
            raise ValueError(
                f"rl.swap_every {self.rl.swap_every} must be >= 1 (learner "
                "updates between param publishes into the serving engine)"
            )
        if self.mesh.seq_devices > 1 and (
            self.train.comm_dtype != "f32" or self.train.comm_overlap
        ):
            # the SP factories take grads OUTSIDE shard_map (the collective
            # transposes already produce global grads) — there is no grad
            # allreduce to compress or overlap
            raise ValueError(
                "train.comm_dtype='bf16' / train.comm_overlap are not "
                "implemented for the sequence-parallel ('seq_devices > 1') "
                "path: its gradients are computed outside shard_map and "
                "never ride a grad allreduce"
            )
        if self.mesh.mp_devices < 1:
            raise ValueError(
                f"mesh.mp_devices {self.mesh.mp_devices} must be >= 1 "
                "(1 = no model parallelism)"
            )
        if self.mesh.mp_devices > 1:
            if self.mesh.seq_devices > 1:
                # both want the second mesh dimension; a 3-D
                # ('data','seq','mp') composition needs an SP-aware vocab
                # shard story first (ROADMAP flagship-XL residuals)
                raise ValueError(
                    "mesh.mp_devices > 1 cannot compose with the "
                    "sequence-parallel ('seq_devices > 1') path yet — "
                    "pick one second mesh axis"
                )
            if self.model.vocab_size % self.mesh.mp_devices:
                raise ValueError(
                    f"mesh.mp_devices {self.mesh.mp_devices} must divide "
                    f"model.vocab_size {self.model.vocab_size} (the vocab "
                    "head and embedding shard in equal slices)"
                )
            if self.model.d_hidden % self.mesh.mp_devices:
                raise ValueError(
                    f"mesh.mp_devices {self.mesh.mp_devices} must divide "
                    f"model.d_hidden {self.model.d_hidden} (the LSTM gate "
                    "matrices shard in equal columns)"
                )
            if (self.mesh.num_devices
                    and self.mesh.num_devices % self.mesh.mp_devices):
                raise ValueError(
                    f"mesh.mp_devices {self.mesh.mp_devices} must divide "
                    f"mesh.num_devices {self.mesh.num_devices} (the mesh "
                    "is a dense data x mp grid)"
                )

    # ---- serialization ----------------------------------------------------

    def to_dict(self) -> dict[str, Any]:
        return dataclasses.asdict(self)

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2, default=list)

    @classmethod
    def from_dict(cls, d: Mapping[str, Any]) -> "ExperimentConfig":
        def build(tp, val):
            if val is None:
                return tp()
            fields = {f.name: f for f in dataclasses.fields(tp)}
            kwargs = {}
            for k, v in val.items():
                if k not in fields:
                    raise KeyError(f"{tp.__name__}: unknown field {k!r}")
                if isinstance(v, list):
                    v = tuple(tuple(x) if isinstance(x, list) else x for x in v)
                kwargs[k] = v
            return tp(**kwargs)

        return cls(
            name=d.get("name", "experiment"),
            model=build(ModelConfig, d.get("model")),
            data=build(DataConfig, d.get("data")),
            train=build(TrainConfig, d.get("train")),
            rl=build(RLConfig, d.get("rl")),
            eval=build(EvalConfig, d.get("eval")),
            mesh=build(MeshConfig, d.get("mesh")),
        )

    @classmethod
    def from_json(cls, s: str) -> "ExperimentConfig":
        return cls.from_dict(json.loads(s))

    def override(self, **dotted: Any) -> "ExperimentConfig":
        """Apply ``section__field=value`` overrides (CLI escape hatch).

        ``cfg.override(model__d_hidden=1024, rl__enabled=True)``
        """
        out = self
        for key, value in dotted.items():
            section, _, fname = key.partition("__")
            if not fname:
                out = dataclasses.replace(out, **{section: value})
                continue
            sub = getattr(out, section)
            out = dataclasses.replace(
                out, **{section: dataclasses.replace(sub, **{fname: value})}
            )
        return out
