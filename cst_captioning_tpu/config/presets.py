"""Named presets — the Makefile-equivalent experiment recipes.

The five presets reproduce, one-for-one, the capability configs recorded by the
driver in ``BASELINE.json`` (the acceptance surface of the rebuild; the
reference expressed these as Makefile targets over ``opts.py`` flags,
SURVEY.md §2 rows 1/12):

1. ``msvd_xe_meanpool``      — MSVD, ResNet-152 mean-pool, 1-layer LSTM, XE.
2. ``msrvtt_xe_attention``   — MSR-VTT, ResNet-152 + C3D, temporal attention, XE.
3. ``msrvtt_scst``           — MSR-VTT CST fine-tune: greedy baseline + CIDEr-D (SCST).
4. ``msrvtt_cst_consensus``  — MSR-VTT weighted-consensus reward (CIDEr-D + BLEU4),
                               5 Monte-Carlo rollouts, self-consensus (SCB) baseline.
5. ``msrvtt_eval_beam5``     — MSR-VTT eval: beam search (beam=5) + COCO metrics.

Two more run the second decoder kind (``model.decoder = "latent_moe"``,
models/latent_moe.py) at the published widths of Kimi-K2-Instruct, cut to one
chip's share of an expert-parallel deployment (``_KIMI_K2_EP32``):

6. ``kimi_k2_ep32_xe``       — the stack behind the MSR-VTT frame features,
                               XE; bfloat16 parameters and plain SGD (no
                               moments), so that constructing it fits a chip.
                               The benchmark makes its seeded policy from it.
7. ``kimi_k2_ep32_eval_beam5`` — the same model, beam-5 eval through the
                               ``Evaluator`` (beams flattened into the batch).

Two more run the third (``model.decoder = "sparse_linear"``,
models/sparse_linear.py) at the published widths of MiniCPM-SALA, eight of
its 32 layers (``_MINICPM_SALA_8L``):

8. ``minicpm_sala_8l_xe``    — the stack behind a 16384-slot video prefix of
                               patch tokens; bfloat16 parameters and plain
                               SGD. The benchmark makes its seeded policy
                               from it (0 steps).
9. ``minicpm_sala_8l_eval_beam5`` — the same model, beam-5 eval through the
                               ``Evaluator``, beams on lanes: a clip's beams
                               share one copy of its prefix.

Two more run the fourth (``model.decoder = "eva"``, models/eva.py) at the
published widths of EvaByte, eight of its 32 layers (``_EVABYTE_8L``):

10. ``evabyte_8l_xe``        — the stack behind the same 16384-slot prefix,
                               a byte vocabulary of 320 and captions of 128
                               steps; bfloat16 parameters and plain SGD. The
                               benchmark makes its seeded policy from it
                               (0 steps).
11. ``evabyte_8l_eval_beam5`` — the same model, beam-5 eval through the
                               ``Evaluator``, beams on lanes: a clip's beams
                               share one copy of its prefix's summaries and
                               of its last window's exact keys.

Two more run the fifth (``model.decoder = "window_moe"``,
models/window_moe.py) at the published widths of MiMo-V2.5, its first eleven
layers as one chip's share of a 16-way expert-parallel deployment
(``_MIMO_V2_5_EP16``):

12. ``mimo_v2_5_ep16_xe``    — the stack behind the same 16384-slot prefix;
                               bfloat16 parameters and plain SGD. The
                               benchmark makes its seeded policy from it
                               (0 steps).
13. ``mimo_v2_5_ep16_eval_beam5`` — the same model, beam-5 eval through the
                               ``Evaluator``, beams on lanes: a clip's beams
                               share one copy of its prefix's keys, and the
                               step takes all lanes at once, so the routed
                               experts walk one list of rows.

Two more run the sixth (``model.decoder = "cca_moe"``, models/cca_moe.py) at
the published widths of ZAYA1-8B, 20 of its 40 layers with every expert and
the whole tied vocabulary (``_ZAYA1_8B_20L``):

14. ``zaya1_8b_20l_xe``      — the stack behind the same 16384-slot prefix;
                               bfloat16 parameters and plain SGD. The
                               benchmark makes its seeded policy from it
                               (0 steps).
15. ``zaya1_8b_20l_eval_beam5`` — the same model, beam-5 eval through the
                               ``Evaluator``, beams on lanes with the step
                               taken for all lanes at once: a clip's beams
                               share one copy of its prefix's latent keys.

Paper CST variant names map onto presets as: XE -> 1/2; CST_GT_None/SCST -> 3;
CST_MS_SCB -> 4 (with ``rl.baseline="scb"``); WXE is preset 2 with
``train.loss="wxe"``.
"""

from __future__ import annotations

import dataclasses

from cst_captioning_tpu.config.config import (
    DataConfig,
    EvalConfig,
    ExperimentConfig,
    ModelConfig,
    RLConfig,
    TrainConfig,
)

# MSR-VTT-scale vocab (reference builds ~8-11k word vocab after thresholding);
# synthetic/test runs override this downward.
_MSVD_VOCAB = 4000
_MSRVTT_VOCAB = 9000


def _msvd_xe_meanpool() -> ExperimentConfig:
    return ExperimentConfig(
        name="msvd_xe_meanpool",
        model=ModelConfig(
            vocab_size=_MSVD_VOCAB,
            modalities=(("resnet", 2048),),
            encoder="meanpool",
            d_embed=512,
            d_hidden=512,
            max_len=30,
            max_frames=28,
        ),
        data=DataConfig(dataset="msvd", batch_size=64),
        train=TrainConfig(loss="xe", lr=1e-4, epochs=50),
    )


def _msrvtt_xe_attention() -> ExperimentConfig:
    return ExperimentConfig(
        name="msrvtt_xe_attention",
        model=ModelConfig(
            vocab_size=_MSRVTT_VOCAB,
            modalities=(("resnet", 2048), ("c3d", 500)),
            encoder="temporal_attention",
            d_embed=512,
            d_hidden=512,
            d_att=256,
            max_len=30,
            max_frames=28,
        ),
        data=DataConfig(dataset="msrvtt", batch_size=64),
        train=TrainConfig(loss="xe", lr=1e-4, epochs=50),
    )


def _msrvtt_scst() -> ExperimentConfig:
    base = _msrvtt_xe_attention()
    return dataclasses.replace(
        base,
        name="msrvtt_scst",
        rl=RLConfig(
            enabled=True,
            num_rollouts=1,
            baseline="greedy",
            reward_cider_weight=1.0,
            reward_bleu4_weight=0.0,
            lr=2e-5,
        ),
    )


def _msrvtt_cst_consensus() -> ExperimentConfig:
    base = _msrvtt_xe_attention()
    return dataclasses.replace(
        base,
        name="msrvtt_cst_consensus",
        rl=RLConfig(
            enabled=True,
            num_rollouts=5,
            baseline="scb",
            reward_cider_weight=1.0,
            reward_bleu4_weight=0.5,
            lr=2e-5,
        ),
    )


def _msrvtt_eval_beam5() -> ExperimentConfig:
    base = _msrvtt_xe_attention()
    return dataclasses.replace(
        base,
        name="msrvtt_eval_beam5",
        eval=EvalConfig(beam_size=5, max_len=30, split="test"),
    )


# Kimi-K2-Instruct (huggingface.co/moonshotai/Kimi-K2-Instruct, config.json):
# every width as published; depth, experts held and vocabulary are one chip's
# share — 1 dense + 6 expert layers of the 61 (the rest are further pipeline
# stages), experts 0-11 of each layer's 384 (32 chips share a layer by expert
# parallelism; attention, shared expert, router and dense layer whole on
# each), 20480 rows of the 163840-word vocabulary. 56 prefix slots + 30
# caption positions of the 131072 the source allows.
_KIMI_K2_EP32 = ModelConfig(
    decoder="latent_moe",
    vocab_size=20480,
    modalities=(("resnet", 2048), ("c3d", 500)),
    max_len=30,
    max_frames=28,
    dropout=0.0,
    dtype="bfloat16",
    param_dtype="bfloat16",
    hidden_size=7168,
    num_hidden_layers=7,
    first_k_dense_replace=1,
    intermediate_size=18432,
    moe_intermediate_size=2048,
    n_routed_experts=384,
    n_shared_experts=1,
    num_experts_per_tok=8,
    routed_scaling_factor=2.827,
    num_attention_heads=64,
    q_lora_rank=1536,
    kv_lora_rank=512,
    qk_nope_head_dim=128,
    qk_rope_head_dim=64,
    v_head_dim=128,
    rms_norm_eps=1e-6,
    rope_theta=50000.0,
    rope_scaling=(
        ("beta_fast", 1), ("beta_slow", 1), ("factor", 32), ("mscale", 1),
        ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096),
    ),
    initializer_range=0.02,
    experts_held=12,
    expert_share_index=0,
)


def _kimi_k2_ep32_xe() -> ExperimentConfig:
    return ExperimentConfig(
        name="kimi_k2_ep32_xe",
        model=_KIMI_K2_EP32,
        data=DataConfig(dataset="msrvtt", batch_size=8),
        # SGD keeps no moments: the state is the bfloat16 parameters alone
        train=TrainConfig(loss="xe", optimizer="sgd", lr=1e-4, epochs=1),
    )


def _kimi_k2_ep32_eval_beam5() -> ExperimentConfig:
    return dataclasses.replace(
        _kimi_k2_ep32_xe(),
        name="kimi_k2_ep32_eval_beam5",
        # beams flattened into the batch: the routed experts take one list of
        # rows a step, where "lanes" would vmap them over the beams
        eval=EvalConfig(beam_size=5, max_len=30, split="test",
                        beam_impl="reference"),
    )


# MiniCPM-SALA (huggingface.co/openbmb/MiniCPM-SALA, config.json): every width
# as published; the depth is the published layers 9-16 (one sparse layer, six
# linear, one sparse: the model's own ratio of 1 to 3), one of four pipeline
# stages of eight layers. The muP depth scale and the linear layers' decay
# slopes keep the published depth (32) and the layers' published indices
# (first_layer_index 8). One modality of 16384 patch tokens: 256 frames x 64
# pooled patches. The selection's sizes are MiniCPM4's ``sparse_config``.
_MINICPM_SALA_8L = ModelConfig(
    decoder="sparse_linear",
    vocab_size=73448,
    modalities=(("patch", 1024),),
    max_len=30,
    max_frames=16384,
    dropout=0.0,
    dtype="bfloat16",
    param_dtype="bfloat16",
    hidden_size=4096,
    num_hidden_layers=8,
    intermediate_size=16384,
    num_attention_heads=32,
    num_key_value_heads=2,
    head_dim=128,
    lightning_nh=32,
    lightning_head_dim=128,
    mixer_types=("minicpm4",) + ("lightning-attn",) * 6 + ("minicpm4",),
    rms_norm_eps=1e-6,
    rope_theta=10000.0,
    initializer_range=0.02,
    scale_emb=12.0,
    scale_depth=1.4,
    dim_model_base=256,
    published_layers=32,
    first_layer_index=8,
    sparse_kernel_size=32,
    sparse_kernel_stride=16,
    sparse_block_size=64,
    sparse_topk=64,
    sparse_window_size=2048,
    sparse_init_blocks=1,
    sparse_dense_len=8192,
)


def _minicpm_sala_8l_xe() -> ExperimentConfig:
    return ExperimentConfig(
        name="minicpm_sala_8l_xe",
        model=_MINICPM_SALA_8L,
        data=DataConfig(dataset="msrvtt", batch_size=2),
        train=TrainConfig(loss="xe", optimizer="sgd", lr=1e-4, epochs=1),
    )


def _minicpm_sala_8l_eval_beam5() -> ExperimentConfig:
    return dataclasses.replace(
        _minicpm_sala_8l_xe(),
        name="minicpm_sala_8l_eval_beam5",
        # beams on lanes: the lane step closes over the encoder output, so
        # the prefix's keys are read from one copy a clip ("reference" would
        # tile them a lane)
        eval=EvalConfig(beam_size=5, max_len=30, split="test",
                        beam_impl="lanes", prefill_program=True),
    )


# EvaByte (huggingface.co/EvaByte/EvaByte, config.json): every width as
# published; eight of the 32 layers (every layer is the same kind: one of four
# pipeline stages of eight). The prefix is the sparse/linear preset's: one
# modality of 16384 patch tokens. 320 ids: the corpus' 4 specials and 316
# symbols; the head holds num_pred_heads 8 blocks of them.
_EVABYTE_8L = ModelConfig(
    decoder="eva",
    vocab_size=320,
    modalities=(("patch", 1024),),
    max_len=128,
    max_frames=16384,
    dropout=0.0,
    dtype="bfloat16",
    param_dtype="bfloat16",
    hidden_size=4096,
    num_hidden_layers=8,
    intermediate_size=11008,
    num_attention_heads=32,
    rms_norm_eps=1e-5,
    rope_theta=100000.0,
    window_size=2048,
    chunk_size=16,
    num_pred_heads=8,
    init_std=0.01275,
)


def _evabyte_8l_xe() -> ExperimentConfig:
    return ExperimentConfig(
        name="evabyte_8l_xe",
        model=_EVABYTE_8L,
        data=DataConfig(dataset="msrvtt", batch_size=2),
        train=TrainConfig(loss="xe", optimizer="sgd", lr=1e-4, epochs=1),
    )


def _evabyte_8l_eval_beam5() -> ExperimentConfig:
    return dataclasses.replace(
        _evabyte_8l_xe(),
        name="evabyte_8l_eval_beam5",
        # beams on lanes, as the sparse/linear preset: what a clip's lanes
        # share is read from one copy
        eval=EvalConfig(beam_size=5, max_len=128, split="test",
                        beam_impl="lanes", prefill_program=True),
    )


# MiMo-V2.5 (huggingface.co/XiaomiMiMo/MiMo-V2.5, config.json): every width
# as published; the depth is the published layers 0-10 (hybrid_layer_pattern:
# full, window x 4, full, window x 5; moe_layer_freq: layer 0 dense, the
# others routed experts), the first pipeline stage. 16 chips share each layer
# by expert parallelism: this one holds experts 0-15 of the 256 and 19072 of
# the 152576 ids. One modality of 16384 patch tokens, the sparse/linear
# preset's prefix.
_MIMO_V2_5_EP16 = ModelConfig(
    decoder="window_moe",
    vocab_size=19072,
    modalities=(("patch", 1024),),
    max_len=30,
    max_frames=16384,
    dropout=0.0,
    dtype="bfloat16",
    param_dtype="bfloat16",
    hidden_size=4096,
    num_hidden_layers=11,
    first_k_dense_replace=1,
    intermediate_size=16384,
    moe_intermediate_size=2048,
    n_routed_experts=256,
    n_shared_experts=0,
    num_experts_per_tok=8,
    routed_scaling_factor=1.0,
    num_attention_heads=64,
    num_key_value_heads=4,
    swa_num_key_value_heads=8,
    head_dim=192,
    v_head_dim=128,
    sliding_window=128,
    partial_rotary_factor=0.334,
    rope_theta=10000000.0,
    swa_rope_theta=10000.0,
    attention_value_scale=0.707,
    rms_norm_eps=1e-5,
    initializer_range=0.02,
    experts_held=16,
    expert_share_index=0,
    mixer_types=("full",) + ("window",) * 4 + ("full",) + ("window",) * 5,
    published_layers=48,
    first_layer_index=0,
)


def _mimo_v2_5_ep16_xe() -> ExperimentConfig:
    return ExperimentConfig(
        name="mimo_v2_5_ep16_xe",
        model=_MIMO_V2_5_EP16,
        data=DataConfig(dataset="msrvtt", batch_size=2),
        train=TrainConfig(loss="xe", optimizer="sgd", lr=1e-4, epochs=1),
    )


def _mimo_v2_5_ep16_eval_beam5() -> ExperimentConfig:
    return dataclasses.replace(
        _mimo_v2_5_ep16_xe(),
        name="mimo_v2_5_ep16_eval_beam5",
        # beams on lanes: a clip's prefix keys are read from one copy, and
        # this kind's step takes all the lanes at once (models/captioner.py
        # ALL_LANES), so its experts walk one list of lanes x clips rows
        eval=EvalConfig(beam_size=5, max_len=30, split="test",
                        beam_impl="lanes", prefill_program=True),
    )


# ZAYA1-8B (huggingface.co/Zyphra/ZAYA1-8B, config.json): every width, all
# 16 experts and the whole tied vocabulary as published; the depth is the
# published layers 0-19 of 40 (every layer is of one kind), the first of two
# pipeline stages. One modality of 16384 patch tokens, the sparse/linear
# preset's prefix.
_ZAYA1_8B_20L = ModelConfig(
    decoder="cca_moe",
    vocab_size=262272,
    modalities=(("patch", 1024),),
    max_len=30,
    max_frames=16384,
    dropout=0.0,
    dtype="bfloat16",
    param_dtype="bfloat16",
    hidden_size=2048,
    num_hidden_layers=20,
    moe_intermediate_size=2048,
    n_routed_experts=16,
    n_shared_experts=0,
    num_experts_per_tok=1,
    num_attention_heads=8,
    num_key_value_heads=2,
    head_dim=128,
    cca_time0=2,
    cca_time1=2,
    partial_rotary_factor=0.5,
    rope_theta=5000000.0,
    router_hidden_size=256,
    tie_word_embeddings=True,
    rms_norm_eps=1e-5,
    initializer_range=0.02,
    experts_held=16,
    expert_share_index=0,
    published_layers=40,
    first_layer_index=0,
)


def _zaya1_8b_20l_xe() -> ExperimentConfig:
    return ExperimentConfig(
        name="zaya1_8b_20l_xe",
        model=_ZAYA1_8B_20L,
        data=DataConfig(dataset="msrvtt", batch_size=2),
        train=TrainConfig(loss="xe", optimizer="sgd", lr=1e-4, epochs=1),
    )


def _zaya1_8b_20l_eval_beam5() -> ExperimentConfig:
    return dataclasses.replace(
        _zaya1_8b_20l_xe(),
        name="zaya1_8b_20l_eval_beam5",
        # beams on lanes, the step taken for all lanes at once (models/
        # captioner.py ALL_LANES): a clip's latent prefix keys are read from
        # one copy and the experts walk one list of lanes x clips rows
        eval=EvalConfig(beam_size=5, max_len=30, split="test",
                        beam_impl="lanes", prefill_program=True),
    )


PRESETS = {
    "msvd_xe_meanpool": _msvd_xe_meanpool,
    "msrvtt_xe_attention": _msrvtt_xe_attention,
    "msrvtt_scst": _msrvtt_scst,
    "msrvtt_cst_consensus": _msrvtt_cst_consensus,
    "msrvtt_eval_beam5": _msrvtt_eval_beam5,
    "kimi_k2_ep32_xe": _kimi_k2_ep32_xe,
    "kimi_k2_ep32_eval_beam5": _kimi_k2_ep32_eval_beam5,
    "minicpm_sala_8l_xe": _minicpm_sala_8l_xe,
    "minicpm_sala_8l_eval_beam5": _minicpm_sala_8l_eval_beam5,
    "evabyte_8l_xe": _evabyte_8l_xe,
    "evabyte_8l_eval_beam5": _evabyte_8l_eval_beam5,
    "mimo_v2_5_ep16_xe": _mimo_v2_5_ep16_xe,
    "mimo_v2_5_ep16_eval_beam5": _mimo_v2_5_ep16_eval_beam5,
    "zaya1_8b_20l_xe": _zaya1_8b_20l_xe,
    "zaya1_8b_20l_eval_beam5": _zaya1_8b_20l_eval_beam5,
}


def get_preset(name: str) -> ExperimentConfig:
    try:
        return PRESETS[name]()
    except KeyError:
        raise KeyError(
            f"unknown preset {name!r}; available: {sorted(PRESETS)}"
        ) from None
