"""The sparse/linear decoder's cost model
(``cost_models/sparse_linear_decoder.py``, PR 38) against arithmetic written
out here, at the published sizes of ``configs/minicpm_sala_8l.json``."""

import pytest

from benchmark import costs
from benchmark.tests import tiny

P, H, D = 16384, 4096, 128


@pytest.fixture(scope="module")
def config():
    return tiny.config_file("minicpm_sala_8l")


@pytest.fixture(scope="module")
def cost(config):
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost")


def test_the_parameter_count_is_issue_38_s(config, cost):
    """A sparse layer 4096 (4096 q + 2 x 256 kv + 4096 gate + 4096 o) + 3 x
    4096 x 16384 = 254 M, a linear layer 285 M, embedding and head 602 M, the
    projector 4.2 M: 2.825 B in matrices (``model.init`` counts 2,824,763,392
    with the norms' vectors, tests/test_sparse_linear.py)."""
    m = config["model"]
    sparse = H * (4096 + 2 * 256 + 4096) + 4096 * H
    linear = H * (4096 + 2 * 4096 + 4096) + 4096 * H
    assert cost.mixer_weights(m, "minicpm4") == sparse == 52_428_800
    assert cost.mixer_weights(m, "lightning-attn") == linear == 83_886_080
    assert cost.ffn_weights(m) == 3 * H * 16384 == 201_326_592
    assert cost.parameter_count(m) == (2 * sparse + 6 * linear + 8 * 3 * H * 16384
                                       + 2 * H * 73448 + 1024 * H)
    assert 2_824_763_392 - cost.parameter_count(m) == 8 * 2 * H + H + 16 * D + 6 * H
    assert cost.n_prefix(m) == P


def test_a_query_attends_to_what_the_rule_gives_it(config, cost):
    m = config["model"]
    assert cost.attended_keys(m, 100) == 100 and cost.attended_keys(m, 8191) == 8191
    # at 8192 keys seen: the window and the first block (2112 keys = 33 of 128
    # blocks) and of the 64 chosen blocks the 95/128 that fall outside them
    assert cost.attended_keys(m, 8192) == pytest.approx(2112 + 64 * 64 * 95 / 128)
    assert 5600 < cost.attended_keys(m, P + 30) < (64 + 1) * 64 + 2048
    assert cost.compressed_seen(m, 8191, P) == 0
    assert cost.compressed_seen(m, 8192, P) == (8192 - 32) // 16 + 1
    assert cost.compressed_seen(m, P + 30, P) == (P - 32) // 16 + 1 == 1023


def test_the_prefix_is_nine_tenths_ffn_and_projections(config, cost):
    m = config["model"]
    whole = cost.prefill_clip_flops(m)
    mixers = cost.prefill_mixer_flops(m, "minicpm4") \
        + 6 * cost.prefill_mixer_flops(m, "lightning-attn")
    assert cost.prefill_mixer_flops(m, "lightning-attn") == P * 4 * 32 * D * D
    # the first sparse layer, six linear and seven FFNs run over the prefix;
    # the last layer leaves its keys and values alone
    dense = P * 2 * (cost.mixer_weights(m, "minicpm4")
                     + 6 * cost.mixer_weights(m, "lightning-attn")
                     + 7 * cost.ffn_weights(m) + 2 * H * 256) + 2 * P * 1024 * H
    assert whole == pytest.approx(dense + mixers, rel=1e-12)
    assert 0.95 < dense / whole < 0.99
    assert 3.9e9 < whole / P < 4.2e9        # about 4 GFLOP a prefix position
    # done dense, one sparse layer's attention alone is 2.2 TFLOP a clip
    assert 4 * 32 * D * P * (P + 1) / 2 == pytest.approx(2.2e12, rel=0.01)
    assert cost.prefill_mixer_flops(m, "minicpm4") < 0.65 * 2.2e12


def test_every_caption_30_long_equals_the_closed_form(config, cost):
    m = config["model"]
    B, W, T = 2, 5, 30
    got = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W})
    assert set(got) == {"eval_prefill", "eval_decode"}
    assert got["eval_prefill"]["flops"] == B * cost.prefill_clip_flops(m)
    assert got["eval_decode"]["flops"] == pytest.approx(
        B * W * sum(cost.step_token_flops(m, t) for t in range(T)), rel=1e-12)
    # a step reads the stack and the head once: 5.05 GB; 30 steps 0.19 s of HBM
    assert cost.weight_bytes(m) == 2 * (cost.parameter_count(m) - H * 73448 - 1024 * H)
    assert got["eval_decode"]["bytes"] > T * cost.weight_bytes(m)
    assert got["eval_decode"]["bytes"] < 1.1 * T * cost.weight_bytes(m)
    least, bound = costs.roofline(got["eval_decode"], "TPU v5 lite")
    assert bound == "hbm" and 0.17 < least < 0.22
    least, bound = costs.roofline(got["eval_prefill"], "TPU v5 lite")
    assert bound == "flops" and 0.6 < least < 0.75
    full = cost.full_profile(T, B, B * W)
    assert costs.program_cost(config, {"kind": "eval", "B": B, "beam": W,
                                       "profile": full}) == got


def test_no_step_past_the_longest_caption_costs_anything(config, cost):
    m = config["model"]
    B, W = 2, 5
    short = {"lanes": [float(B * W)] * 10 + [0.0] * 20,
             "clips": [float(B)] * 10 + [0.0] * 20,
             "steps": [1.0] * 10 + [0.0] * 20}
    got = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W,
                                      "profile": short})
    assert got["eval_decode"]["flops"] == pytest.approx(
        B * W * sum(cost.step_token_flops(m, t) for t in range(10)), rel=1e-12)
    whole = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W})
    assert got["eval_prefill"] == whole["eval_prefill"]
    assert got["eval_decode"]["bytes"] < whole["eval_decode"]["bytes"] \
        - 19 * cost.weight_bytes(m)


def test_the_mechanisms_costs_are_the_layers_that_run_over_the_prefix(config, cost):
    m = config["model"]
    mech = cost.mechanism_cost(m, {"B": 2})
    assert mech["sparse_attn"]["flops"] == 2 * cost.prefill_mixer_flops(m, "minicpm4")
    assert mech["linear_attn"]["flops"] == 2 * 6 * cost.prefill_mixer_flops(
        m, "lightning-attn")
    # q and the output 32 heads, k and v 2: 68 x 128 numbers a position
    assert mech["sparse_attn"]["bytes"] == 2 * P * 68 * D * 2 + 2 * 1024 * 2 * D * 4


def test_the_cost_model_says_which_job_it_knows(config):
    with pytest.raises(ValueError, match="job eval alone"):
        costs.program_cost(config, {"kind": "xe", "B": 8})
    with pytest.raises(ValueError, match="steps"):
        costs.program_cost(config, {"kind": "eval", "B": 8, "beam": 5,
                                    "profile": {"lanes": [1.0], "clips": [1.0],
                                                "steps": [1.0]}})
