"""The window/full-attention, routed-expert decoder's cost model
(``cost_models/window_moe_decoder.py``, PR 45) against arithmetic written out
here, at the published sizes of ``configs/mimo_v2_5_ep16.json``: the parameter
count is ``model.init``'s, the band's pairs are counted by brute force at
small sizes, and neither mechanism's cost is above what its kernel walks."""

import numpy as np
import pytest

from benchmark import costs
from benchmark.tests import tiny

P, H, V, T, W = 16384, 4096, 19072, 30, 128
ATTN_FULL = H * 64 * 192 + H * 4 * 320 + 64 * 128 * H
ATTN_WINDOW = H * 64 * 192 + H * 8 * 320 + 64 * 128 * H
EXPERT, DENSE = 3 * H * 2048, 3 * H * 16384
PAIR = 2 * 64 * (192 + 128)


@pytest.fixture(scope="module")
def config():
    return tiny.config_file("mimo_v2_5_ep16")


@pytest.fixture(scope="module")
def cost(config):
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost")


def test_the_parameter_count_is_model_init_s(config, cost):
    """Attention 89.13 M (full) / 94.37 M (window); router 1.05 M; 16 experts
    x 25.17 M; dense FFN 201.33 M: 5,426,478,144 with the norms, sinks and
    router biases, what ``model.init`` declares at the published widths
    (tests/test_window_moe.py counts the same tree) and at the tiny ones."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import ModelConfig
    from cst_captioning_tpu.models import CaptionModel

    m = config["model"]
    assert (cost.attention_weights(m, False), cost.attention_weights(m, True)) \
        == (ATTN_FULL, ATTN_WINDOW) == (89_128_960, 94_371_840)
    assert cost.expert_weights(m) == EXPERT == 25_165_824
    assert cost.dense_ffn_weights(m) == DENSE == 201_326_592
    assert cost.attention_weights(m, True, kv_only=True) == H * 8 * 320
    assert cost.parameter_count(m) == 5_426_478_144
    assert cost.kinds(m) == [(False, True)] + [(True, False)] * 4 \
        + [(False, False)] + [(True, False)] * 5
    for sizes in (m, config["tiny"]["model"]):
        mc = ModelConfig(**{k: tuple(map(tuple, v)) if k == "modalities" else v
                            for k, v in sizes.items()})
        model = CaptionModel(mc)
        width = sizes["modalities"][0][1]
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), {"patch": jnp.zeros((1, 8, width))},
            {"patch": jnp.ones((1, 8))},
            jnp.zeros((1, sizes["max_len"]), jnp.int32)))
        assert cost.parameter_count(sizes) == sum(
            x.size for x in jax.tree.leaves(shapes))


@pytest.mark.parametrize("window", [1, 5, 8, 40])
def test_the_band_s_pairs_are_the_brute_force_count(config, cost, window):
    m = dict(config["model"], sliding_window=window)
    for positions in (0, 1, 7, 8, 9, 33):
        i, j = np.arange(positions)[:, None], np.arange(positions)[None]
        band = ((j <= i) & (j > i - window)).sum()
        assert cost.prefix_pairs(m, positions, True) == band
        assert cost.prefix_pairs(m, positions, False) == (j <= i).sum()
        assert cost.prefix_pairs(m, positions, True) == sum(
            cost.attended(m, p, True) for p in range(positions))
    assert cost.attended(m, 1000, True) == window
    assert cost.attended(m, 1000, False) == 1001


def test_the_published_band_is_a_hundredth_of_the_causal_pairs(config, cost):
    m = config["model"]
    assert cost.prefix_pairs(m, P, True) == W * (W + 1) // 2 + (P - W) * W
    assert cost.prefix_pairs(m, P, False) == P * (P + 1) // 2
    # ISSUE 45's clip of 14.3 k positions: nine window layers and two full
    # ones attend 19.6 % of what eleven causal layers would
    n = 14300
    near, whole = cost.prefix_pairs(m, n, True), cost.prefix_pairs(m, n, False)
    assert (9 * near + 2 * whole) / (11 * whole) == pytest.approx(0.196, abs=0.001)
    assert cost.pair_flops(m) == PAIR == 40960


def test_neither_mechanism_cost_is_above_what_its_kernel_walks(config, cost):
    """The kernels walk whole tiles: for every query tile the key tiles its
    band (or the diagonal) reaches, each a full ``tq x tk`` product; the cost
    model counts the pairs inside the band and no other, so a roofline share
    taken against it cannot pass 100 % by the count."""
    from cst_captioning_tpu.ops import window_attention as wa

    m = config["model"]
    mech = cost.mechanism_cost(m, {"B": 2})
    for name, window, tiles, runs in (
            ("window_attn", W, wa.WINDOW_TILES, 8),
            ("full_attn", None, wa.FULL_TILES, 2)):
        tq, tk = tiles
        first, last, steps = wa._walk(P, tq, tk, window)
        qi = np.arange(P // tq)
        walked = int((np.asarray(last(qi)) - np.asarray(first(qi)) + 1).sum()) \
            * tq * tk
        counted = mech[name]["flops"] / (2 * runs * PAIR)
        assert counted == cost.prefix_pairs(m, P, window is not None)
        assert counted <= walked
        assert steps * (P // tq) * tq * tk >= walked       # the grid's extent
    # the band's tiles are three times its pairs, the diagonal's 1.03 times
    assert mech["window_attn"]["flops"] == 2 * 8 * PAIR * (
        W * (W + 1) // 2 + (P - W) * W)
    assert mech["full_attn"]["flops"] == 2 * 2 * PAIR * P * (P + 1) // 2
    # q, k, v read once and the output written, every layer that runs
    assert mech["window_attn"]["bytes"] == 2 * 8 * P * (64 * 320 + 8 * 320) * 2
    assert mech["full_attn"]["bytes"] == 2 * 2 * P * (64 * 320 + 4 * 320) * 2
    assert costs.roofline(mech["full_attn"], "TPU v5 lite")[1] == "flops"


def test_the_prefix_is_matrices_and_two_full_layers_pairs(config, cost):
    m = config["model"]
    whole = cost.prefill_clip_flops(m)
    moe = 2 * H * 256 + 2 * EXPERT * (8 * 16 / 256)
    assert cost.ffn_flops(m, False) == moe and cost.held_share(m) == 0.5
    pairs = PAIR * (8 * cost.prefix_pairs(m, P, True)
                    + 2 * cost.prefix_pairs(m, P, False))
    # the last layer (a window one) leaves its keys and values only
    assert whole == 2 * P * 1024 * H + P * (
        2 * (2 * ATTN_FULL + 8 * ATTN_WINDOW) + 2 * DENSE + 9 * moe) \
        + pairs + P * 2 * H * 8 * 320
    got = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5})
    assert got["eval_prefill"]["flops"] == 2 * whole
    assert 105e12 < got["eval_prefill"]["flops"] < 108e12
    assert 0.20 < 2 * pairs / got["eval_prefill"]["flops"] < 0.23
    # done as dense causal attention the window layers would be four times
    # the two full layers'
    assert 8 * cost.prefix_pairs(m, P, False) * PAIR * 2 > 85e12


def test_a_decode_step_reads_the_weights_it_reaches_and_the_clip_s_keys_once(
        config, cost):
    """30 steps of 10 lanes: a step reads the attention, dense, router and
    head weights and, of each expert layer's 16 held experts, the 4.4 that 10
    lanes' 80 assignments reach by expectation; the full layers' prefix keys
    once a clip; bound by memory."""
    m = config["model"]
    got = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5})["eval_decode"]
    reached = 16 * (1 - (1 - 8 / 256) ** 10)
    assert cost.experts_reached(m, 10) == pytest.approx(reached)
    assert 4.3 < reached < 4.4
    weights = 2 * (2 * ATTN_FULL + 9 * ATTN_WINDOW + DENSE
                   + 10 * (H * 256 + reached * EXPERT) + H * V)
    assert cost.weight_bytes(m, rows=10) == pytest.approx(weights)
    assert 4.8e9 < weights < 4.9e9      # ISSUE 45: about 4.8 GB a step
    row_full, row_window = 4 * 320 * 2, 8 * 320 * 2
    assert (cost.kv_row_bytes(m, False), cost.kv_row_bytes(m, True)) == \
        (row_full, row_window)
    want = 0.0
    for t in range(T):
        shared = 2 * row_full * P + 9 * row_window * (W - 1 - t)
        want += weights + 2 * shared \
            + 10 * (t + 2) * (2 * row_full + 9 * row_window) + 2 * 10 * V * 4
    assert got["bytes"] == pytest.approx(want)
    least, bound = costs.roofline(got, "TPU v5 lite")
    assert bound == "hbm" and 0.15 < least < 0.2
    # the prefix: every held expert once a batch
    pre = cost.weight_bytes(m)
    assert pre == 2 * (1024 * H + 2 * ATTN_FULL + 8 * ATTN_WINDOW + DENSE
                       + 9 * (H * 256 + 16 * EXPERT) + H * 8 * 320)


def test_a_profile_of_shorter_captions_costs_less_and_a_wrong_one_is_refused(
        config, cost):
    m = config["model"]
    full = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5})
    half = cost.full_profile(T, 2, 10)
    for key in half:
        half[key] = [v if t < 15 else 0.0 for t, v in enumerate(half[key])]
    short = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5,
                                  "profile": half})
    assert short["eval_prefill"] == full["eval_prefill"]
    assert 0.49 < short["eval_decode"]["bytes"] / full["eval_decode"]["bytes"] < 0.51
    with pytest.raises(ValueError, match="steps"):
        cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5,
                              "profile": cost.full_profile(12, 2, 10)})
    with pytest.raises(ValueError, match="job eval alone"):
        cost.program_cost(m, {"kind": "cst", "B": 2, "K": 5, "chunks": 1})


# the catalog row's ``config`` (model-configs/architectures.jsonl,
# "MiMo-V2.5"): the source's config.json without the keys that say nothing
# about its shape
SOURCE = "https://huggingface.co/XiaomiMiMo/MiMo-V2.5/blob/main/config.json"
SOURCE_CONFIG = {
    "attention_bias": False,
    "attention_chunk_size": 128,
    "attention_value_scale": 0.707,
    "attention_projection_layout": "fused_qkv",
    "add_full_attention_sink_bias": False,
    "add_swa_attention_sink_bias": True,
    "swa_num_key_value_heads": 8,
    "swa_num_attention_heads": 64,
    "swa_head_dim": 192,
    "swa_v_head_dim": 128,
    "head_dim": 192,
    "hidden_act": "silu",
    "hidden_size": 4096,
    "hybrid_block_size": None,
    "hybrid_layer_pattern": [
        0,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0,
        1,
        1,
        1,
        1,
        1,
        0
    ],
    "intermediate_size": 16384,
    "layernorm_epsilon": 1e-05,
    "max_position_embeddings": 1048576,
    "model_type": "mimo_v2",
    "moe_intermediate_size": 2048,
    "moe_layer_freq": [
        0,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1,
        1
    ],
    "n_group": 1,
    "n_routed_experts": 256,
    "n_shared_experts": None,
    "norm_topk_prob": True,
    "num_attention_heads": 64,
    "num_experts_per_tok": 8,
    "num_hidden_layers": 48,
    "num_key_value_heads": 4,
    "partial_rotary_factor": 0.334,
    "rope_scaling": {
        "rope_type": "default",
        "type": "default"
    },
    "rope_theta": 10000000,
    "routed_scaling_factor": None,
    "scoring_func": "sigmoid",
    "sliding_window": 128,
    "sliding_window_size": 128,
    "swa_rope_theta": 10000,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "topk_method": "noaux_tc",
    "v_head_dim": 128,
    "vocab_size": 152576
}


def test_the_file_holds_the_source_s_widths_and_says_what_it_cut(config):
    assert config["source"] == SOURCE
    differs = {k for k, v in SOURCE_CONFIG.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "n_routed_experts", "vocab_size"} \
        == set(config["reduced"])
    assert config["published"] == {"num_hidden_layers": 48,
                                   "n_routed_experts": 256, "vocab_size": 152576}
    assert (config["num_hidden_layers"], config["n_routed_experts"],
            config["vocab_size"]) == (11, 16, 19072)
    m = config["model"]
    assert (m["num_hidden_layers"], m["experts_held"], m["vocab_size"],
            m["n_routed_experts"]) == (11, 16, 19072, 256)
    assert "16 chips share each layer" in config["deployment"]
    for key in ("rope_pairing", "value_scale", "sink", "window", "qk_norm",
                "initializer_range", "left_out", "missing_slots",
                "caption_keys", "weights"):
        assert config["assumed"][key]
    # every published width unchanged, under the program's names
    for key in ("hidden_size", "intermediate_size", "moe_intermediate_size",
                "num_attention_heads", "num_key_value_heads",
                "swa_num_key_value_heads", "head_dim", "v_head_dim",
                "sliding_window", "partial_rotary_factor", "rope_theta",
                "swa_rope_theta", "attention_value_scale",
                "num_experts_per_tok"):
        assert m[key] == SOURCE_CONFIG[key]
    assert m["rms_norm_eps"] == SOURCE_CONFIG["layernorm_epsilon"]
    # the held layers are the published layers 0-10, kinds from the pattern
    held = ["window" if k else "full"
            for k in SOURCE_CONFIG["hybrid_layer_pattern"][:11]]
    assert m["mixer_types"] == held and m["first_layer_index"] == 0
    assert SOURCE_CONFIG["moe_layer_freq"][:11] == [0] + [1] * 10
    assert m["first_k_dense_replace"] == 1 and m["n_shared_experts"] == 0
