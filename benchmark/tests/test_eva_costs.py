"""The EVA decoder's cost model (``cost_models/eva_decoder.py``, PR 42)
against arithmetic written out here, at the published sizes of
``configs/evabyte_8l.json``."""

import pytest

from benchmark import costs
from benchmark.tests import tiny

P, H, M, V, T = 16384, 4096, 11008, 320, 128
LAYER = 4 * H * H + 3 * H * M


@pytest.fixture(scope="module")
def config():
    return tiny.config_file("evabyte_8l")


@pytest.fixture(scope="module")
def cost(config):
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost")


def test_the_parameter_count_is_issue_42_s(config, cost):
    """A layer 4 x 4096^2 + 3 x 4096 x 11008 = 202.4 M in matrices (with its
    two norms and the heads' phi and mu 202,391,552); embedding 1.3 M, the
    eight-block head 10.5 M, the projector 4.2 M: 1.635 B (``model.init``
    counts 1,635,127,296, tests/test_eva.py)."""
    m = config["model"]
    assert cost.layer_weights(m) == LAYER == 202_375_168
    assert 202_391_552 - LAYER == 2 * H + 2 * 32 * 128
    assert cost.layer_weights(m, kv_only=True) == 2 * H * H
    assert cost.parameter_count(m) == 8 * LAYER + H * V * 9 + 1024 * H
    assert 1_635_127_296 - cost.parameter_count(m) == 8 * (2 * H + 2 * 32 * 128) + H
    assert cost.n_prefix(m) == P


def test_a_query_attends_to_its_window_s_keys_and_the_summaries_before(
        config, cost):
    m = config["model"]
    assert cost.attended(m, 0) == (1, 0) and cost.attended(m, 2047) == (2048, 0)
    assert cost.attended(m, 2048) == (1, 128)
    assert cost.attended(m, 14336 + 23) == (24, 896)
    assert cost.attended(m, P + 127) == (128, 1024)
    # ISSUE 42's clip of 14336 positions: 7 x 2048 x 2049 / 2 exact pairs and
    # 2048 x 128 x 21 with summaries, 20.2 M a head
    exact, pooled = cost.prefix_pairs(m, 14336)
    assert exact == 7 * 2048 * 2049 // 2 and pooled == 2048 * 128 * 21
    assert exact + pooled == pytest.approx(20.2e6, rel=0.005)
    # the closed form is the sum it stands for
    assert cost.prefix_pairs(m, 5000) == tuple(map(sum, zip(
        *(cost.attended(m, i) for i in range(5000)))))
    # as dense causal attention the same clip would be five times the pairs
    assert 14336 * 14337 / 2 / (exact + pooled) == pytest.approx(5.1, abs=0.1)


def test_the_prefix_is_a_twentieth_attention_and_the_rest_matrices(config, cost):
    m = config["model"]
    whole = cost.prefill_clip_flops(m)
    pairs = 7 * cost.pair_flops(m) * sum(cost.prefix_pairs(m, P))
    assert cost.pair_flops(m) == 4 * H          # 32 heads x 128 x 2 x 2
    assert whole == 2 * P * 1024 * H + 7 * P * 2 * LAYER + pairs \
        + P * 2 * 2 * H * H
    assert 0.05 < pairs / whole < 0.07
    # two clips: 100 TFLOP, half a second of the chip's peak
    shape = {"kind": "eval", "B": 2, "beam": 5}
    got = cost.program_cost(m, shape)
    assert got["eval_prefill"]["flops"] == 2 * whole
    assert 95e12 < got["eval_prefill"]["flops"] < 105e12
    mech = cost.mechanism_cost(m, shape)["eva_attn"]
    assert mech["flops"] == 2 * pairs
    assert mech["bytes"] == 2 * 7 * (4 * P + 2 * P // 16) * H * 2
    assert costs.roofline(mech, "TPU v5 lite")[1] == "flops"


def test_a_decode_step_reads_the_weights_and_the_clip_s_state_once(config, cost):
    """128 steps of 10 lanes: a step reads 3.24 GB of weights, the clip's
    1024 summaries once a clip (a clip of 16384 valid slots ends on a
    window's edge and has no exact keys left in its window: the model's
    least) and a lane's own caption keys a lane; bound by memory."""
    m = config["model"]
    shape = {"kind": "eval", "B": 2, "beam": 5}
    got = cost.program_cost(m, shape)["eval_decode"]
    weights = 2 * (8 * LAYER + H * V)
    assert cost.weight_bytes(m) == weights
    cell = 8 * 2 * H * 2                        # a key and a value, 8 layers
    want = 0.0
    for t in range(T):
        want += weights + cell * (2 * 1024 + 10 * (t + 1 + 1)) + 2 * 10 * V * 4
    assert got["bytes"] == pytest.approx(want)
    flops = sum(10 * (2 * H * V + 8 * (2 * LAYER + 4 * H * (t + 1 + 1024)))
                for t in range(T))
    assert got["flops"] == pytest.approx(flops)
    least, bound = costs.roofline(got, "TPU v5 lite")
    assert bound == "hbm" and 0.5 < least < 0.6
    # the clip's state once a clip is 13 % of a step's bytes at the corpus'
    # mean (896 summaries + 1024 exact keys) and 7 % in this least case
    assert 0.06 < cell * 2 * 1024 / (want / T) < 0.08


def test_a_profile_of_shorter_captions_costs_less_and_a_wrong_one_is_refused(
        config, cost):
    m = config["model"]
    full = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5})
    half = cost.full_profile(T, 2, 10)
    for key in half:
        half[key] = [v if t < 64 else 0.0 for t, v in enumerate(half[key])]
    short = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5,
                                  "profile": half})
    assert short["eval_prefill"] == full["eval_prefill"]
    assert 0.49 < short["eval_decode"]["bytes"] / full["eval_decode"]["bytes"] < 0.5
    with pytest.raises(ValueError, match="steps"):
        cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5,
                              "profile": cost.full_profile(30, 2, 10)})
    with pytest.raises(ValueError, match="job eval alone"):
        cost.program_cost(m, {"kind": "cst", "B": 2, "K": 5, "chunks": 1})


# the catalog row's ``config`` (model-configs/architectures.jsonl, "EvaByte"):
# the source's config.json without the keys that say nothing about its shape
SOURCE = "https://huggingface.co/EvaByte/EvaByte/blob/main/config.json"
SOURCE_CONFIG = {
    "attention_bias": False,
    "attention_class": "eva",
    "chunk_size": 16,
    "fp32_ln": False,
    "fp32_logits": True,
    "fp32_skip_add": True,
    "hidden_act": "silu",
    "hidden_size": 4096,
    "init_cutoff_factor": None,
    "init_fn": "v2",
    "init_std": 0.01275,
    "intermediate_size": 11008,
    "lazy_init": True,
    "max_position_embeddings": 32768,
    "max_seq_length": 32768,
    "mixedp_attn": True,
    "model_type": "evabyte",
    "norm_add_unit_offset": True,
    "num_attention_heads": 32,
    "num_chunks": None,
    "num_hidden_layers": 32,
    "num_key_value_heads": 32,
    "num_pred_heads": 8,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 100000,
    "tie_word_embeddings": False,
    "vocab_size": 320,
    "window_size": 2048
}


def test_the_file_holds_the_source_s_widths_and_says_what_it_cut(config):
    assert config["source"] == SOURCE
    differs = {k for k, v in SOURCE_CONFIG.items() if config.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(config["reduced"])
    assert config["published"]["num_hidden_layers"] == 32
    assert config["num_hidden_layers"] == config["model"]["num_hidden_layers"] == 8
    assert "Four pipeline stages of eight layers" in config["deployment"]
    for key in ("pooling", "sets", "mixedp_attn", "head_layout", "ids",
                "projector", "missing_slots", "weights"):
        assert config["assumed"][key]
    for key in ("window_size", "chunk_size", "num_pred_heads", "init_std",
                "hidden_size", "rope_theta", "intermediate_size",
                "num_attention_heads", "rms_norm_eps", "vocab_size"):
        assert config["model"][key] == SOURCE_CONFIG[key]
