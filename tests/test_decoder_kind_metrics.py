"""What the ``Evaluator`` reports of each language-model decoder kind under
``obs``: every gauge its ``_observe_decode`` sets and every counter (and the
histogram's count and sum) its ``_count`` increments, by name and by value, on a
seeded two-batch CPU pass at the sizes ``tests/test_<kind>.py`` run the kind
at. ``benchmark/``'s readers and ``obs/report.py`` find these by name; a
refactor that moves them behind a decoder-block seam (ROADMAP R0) has to leave
every one as it is, and this file is what says so.
"""

import dataclasses

import jax
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.config import get_preset
from cst_captioning_tpu.config.config import EvalConfig, ModelConfig
from cst_captioning_tpu.models import CaptionModel

T = 8
YARN = (("type", "yarn"), ("factor", 32.0), ("beta_fast", 1.0),
        ("beta_slow", 1.0), ("mscale", 1.0), ("mscale_all_dim", 1),
        ("original_max_position_embeddings", 4096))
_COMMON = dict(vocab_size=32, max_len=T, dtype="float32", param_dtype="float32",
               hidden_size=32, intermediate_size=48, num_attention_heads=4)
# the sizes tests/test_<kind>.py run each kind at, and its evaluation preset
KINDS = {
    "latent_moe": ("kimi_k2_ep32_eval_beam5", dict(
        _COMMON, modalities=(("resnet", 32), ("c3d", 16)), max_frames=8,
        num_hidden_layers=3, first_k_dense_replace=1, moe_intermediate_size=16,
        n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4,
        routed_scaling_factor=2.827, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        rms_norm_eps=1e-6, rope_theta=50000.0, rope_scaling=YARN,
        experts_held=4, expert_share_index=0, initializer_range=0.3)),
    "sparse_linear": ("minicpm_sala_8l_eval_beam5", dict(
        _COMMON, modalities=(("patch", 16),), max_frames=48,
        num_hidden_layers=4, num_key_value_heads=2, head_dim=8, lightning_nh=4,
        lightning_head_dim=8,
        mixer_types=("minicpm4", "lightning-attn", "lightning-attn", "minicpm4"),
        rms_norm_eps=1e-6, rope_theta=10000.0, initializer_range=0.3,
        scale_emb=12.0, scale_depth=1.4, dim_model_base=8, published_layers=32,
        first_layer_index=8, sparse_kernel_size=4, sparse_kernel_stride=2,
        sparse_block_size=4, sparse_topk=2, sparse_window_size=6,
        sparse_init_blocks=1, sparse_dense_len=16)),
    "eva": ("evabyte_8l_eval_beam5", dict(
        _COMMON, modalities=(("patch", 16),), max_frames=48,
        num_hidden_layers=3, rms_norm_eps=1e-5, rope_theta=100000.0,
        window_size=8, chunk_size=2, num_pred_heads=8, init_std=0.3)),
    "window_moe": ("mimo_v2_5_ep16_eval_beam5", dict(
        _COMMON, modalities=(("patch", 16),), max_frames=48,
        num_hidden_layers=4, first_k_dense_replace=1, moe_intermediate_size=16,
        n_routed_experts=16, n_shared_experts=0, num_experts_per_tok=4,
        routed_scaling_factor=1.0, num_attention_heads=8, num_key_value_heads=2,
        swa_num_key_value_heads=4, head_dim=12, v_head_dim=8, sliding_window=8,
        partial_rotary_factor=0.334, rope_theta=1e7, swa_rope_theta=1e4,
        attention_value_scale=0.707, rms_norm_eps=1e-5, initializer_range=0.3,
        experts_held=4, expert_share_index=1,
        mixer_types=("full", "window", "window", "full"), published_layers=48,
        first_layer_index=0)),
    "cca_moe": ("zaya1_8b_20l_eval_beam5", dict(
        _COMMON, modalities=(("patch", 16),), max_frames=48,
        num_hidden_layers=3, moe_intermediate_size=16, n_routed_experts=8,
        n_shared_experts=0, num_experts_per_tok=1, num_attention_heads=8,
        num_key_value_heads=2, head_dim=8, cca_time0=2, cca_time1=2,
        partial_rotary_factor=0.5, rope_theta=5e6, router_hidden_size=8,
        tie_word_embeddings=True, rms_norm_eps=1e-5, initializer_range=0.3,
        experts_held=4, expert_share_index=1, published_layers=40,
        first_layer_index=0)),
}


def _dataset(tmp_path, cfg: ModelConfig, videos: int = 8):
    from cst_captioning_tpu.data.dataset import CaptionDataset
    from cst_captioning_tpu.data.synthetic import make_synthetic_dataset

    paths = make_synthetic_dataset(
        str(tmp_path / "data"), num_videos=videos, vocab_words=24,
        modalities=dict(cfg.modalities), max_frames=cfg.max_frames,
        splits=(1.0, 0.0), seed=3)
    return CaptionDataset(
        paths["info_json"], {name: paths[name] for name, _ in cfg.modalities},
        "train", cfg.max_frames)


def _observed_pass(tmp_path, cfg: ModelConfig, eval_cfg: EvalConfig):
    """One ``Evaluator.evaluate`` over 8 clips in two batches of 4, seeded
    weights, under ``obs`` -> (the result, the metrics' snapshot)."""
    from cst_captioning_tpu.data.batcher import Batcher
    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.train.steps import batch_arrays

    model = CaptionModel(cfg)
    ds = _dataset(tmp_path, cfg)
    eval_cfg = dataclasses.replace(
        eval_cfg, max_len=cfg.max_len, metrics=("CIDEr-D",), split="train")
    sample = next(iter(Batcher(ds, batch_size=2, max_len=cfg.max_len,
                               mode="video").epoch(False)))
    feats, masks, labels, *_ = batch_arrays(sample)
    params = model.init(jax.random.key(0), feats, masks, labels)
    obs.REGISTRY.reset()        # counters are cumulative: this pass's alone
    obs.configure(str(tmp_path / "obs"), run="t")
    try:
        result = Evaluator(model, ds, eval_cfg, batch_size=4).evaluate(params)
        snap = obs.snapshot()
    finally:
        obs.shutdown()
        obs.REGISTRY.reset()
        ds.close()
    return result, snap


# every gauge ``Evaluator._observe_decode`` sets for the kind and every counter
# (and the histogram's count and sum) its ``_count`` increments, with the value
# commit ac84f10's run of ``_observed_pass`` gave
PARENT = {
    "latent_moe": {
        "gauges": {"decode.cache_bytes": 139120.0, "moe.experts_held": 4.0},
        "counters": {"moe.assignments": 2888.0, "moe.assignments.local": 591.0,
                     "moe.expert_rows.count": 16, "moe.expert_rows.sum": 591.0}},
    "sparse_linear": {
        "gauges": {"decode.cache_bytes": 142848.0, "decode.kv_bytes": 90112.0,
                   "decode.index_bytes": 11776.0, "decode.state_bytes": 40960.0},
        "counters": {"sparse.keys_visible": 63070.0,
                     "sparse.keys_selected": 29536.0,
                     "sparse.dense_fallback_queries": 240.0}},
    "eva": {
        "gauges": {"decode.cache_bytes": 328704.0, "decode.window_bytes": 178176.0,
                   "decode.summary_bytes": 150528.0},
        "counters": {"eva.keys_exact": 2703.0, "eva.keys_summary": 7916.0,
                     "eva.window_crossings": 40.0}},
    "window_moe": {
        "gauges": {"decode.cache_bytes": 235520.0,
                   "decode.prefix_key_bytes": 61440.0,
                   "decode.window_bytes": 122880.0, "moe.experts_held": 4.0},
        "counters": {"attn.pairs_window": 9296.0, "attn.pairs_full": 37070.0,
                     "attn.pairs_causal": 74140.0, "moe.assignments": 6152.0,
                     "moe.assignments.local": 1717.0,
                     "moe.expert_rows.count": 24, "moe.expert_rows.sum": 1717.0}},
    # the sixth kind came with PR 50: the values its own commit's run gave
    # (4 clips x 3 layers x 2 heads x 48 positions x 8 numbers of keys and of
    # values once a clip; 20 lanes' tails of 168 numbers a layer; 20 lanes'
    # 8 caption positions)
    "cca_moe": {
        "gauges": {"decode.cache_bytes": 175488.0,
                   "decode.prefix_key_bytes": 73728.0,
                   "decode.conv_tail_bytes": 40320.0, "moe.experts_held": 4.0},
        "counters": {"attn.pairs_causal": 55605.0, "moe.assignments": 1538.0,
                     "moe.assignments.local": 929.0,
                     "moe.assignments.skipped": 377.0,
                     "moe.expert_rows.count": 24, "moe.expert_rows.sum": 929.0}},
}


@pytest.fixture(scope="module")
def snapshots(tmp_path_factory):
    made = {}

    def of(kind: str):
        if kind not in made:
            preset, tiny = KINDS[kind]
            made[kind] = _observed_pass(
                tmp_path_factory.mktemp(kind), ModelConfig(decoder=kind, **tiny),
                get_preset(preset).eval)[1]
        return made[kind]

    return of


@pytest.mark.parametrize("what", ["gauges", "counters"])
@pytest.mark.parametrize("kind", sorted(KINDS))
def test_a_decoder_kind_sets_its_gauges_and_counters(snapshots, kind, what):
    snap, want = snapshots(kind), PARENT[kind][what]
    assert want
    if what == "gauges":
        got = {name: snap["gauges"].get(name) for name in want}
    else:
        rows = snap["histograms"].get("moe.expert_rows", {})
        got = {name: snap["counters"].get(name) for name in want
               if not name.startswith("moe.expert_rows.")}
        got.update({f"moe.expert_rows.{k}": rows.get(k) for k in ("count", "sum")
                    if f"moe.expert_rows.{k}" in want})
    assert got == want


@pytest.mark.parametrize("kind", ["cca_moe", "latent_moe", "window_moe"])
def test_a_routed_expert_kind_counts_the_row_tiles_of_the_tally_it_read(
        tmp_path, kind, monkeypatch):
    """``moe.rows_a_tile`` is the grouped product's tile at a search step's
    rows (a beam of 5 over a batch of 4), and ``moe.row_tiles`` the sum over
    layers and held experts of ``ceil(rows / tile)`` of every batch's tally
    ``Evaluator._count`` read, so that ``moe.assignments.local / (moe.row_tiles
    x moe.rows_a_tile)`` is the tiles' fill."""
    import numpy as np

    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.models.experts import expert_tile_rows

    read, count = [], Evaluator._count

    def tapped(self):
        if self._tallies:
            first = self._tallies[0]
            read.append(np.asarray(first[0] if isinstance(first, tuple) else first))
        count(self)

    monkeypatch.setattr(Evaluator, "_count", tapped)
    preset, tiny = KINDS[kind]
    cfg = ModelConfig(decoder=kind, **tiny)
    _result, snap = _observed_pass(tmp_path, cfg, get_preset(preset).eval)
    tile = expert_tile_rows(5 * 4, cfg.num_experts_per_tok, cfg.n_routed_experts)
    assert snap["gauges"]["moe.rows_a_tile"] == tile == (16 if kind == "cca_moe" else 32)
    assert len(read) == 2 and all(t.shape[1] == cfg.experts_held + 1 for t in read)
    tiles = sum(int(np.ceil(t[:, :-1] / tile).sum()) for t in read)
    assert snap["counters"]["moe.row_tiles"] == tiles > 0
    local = snap["counters"]["moe.assignments.local"]
    assert local == sum(int(t[:, :-1].sum()) for t in read)
    assert 0 < local / (tiles * tile) <= 1
