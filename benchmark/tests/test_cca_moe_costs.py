"""The compressed-latent, top-1-expert decoder's cost model
(``cost_models/cca_moe_decoder.py``, PR 50) against arithmetic written out
here, at the published sizes of ``configs/zaya1_8b_20l.json``: the parameter
count is ``model.init``'s, the mechanism's cost is not above what its kernel
walks, and the programs' FLOPs and bytes are the sums the module's docstring
names."""

import pytest

from benchmark import costs
from benchmark.tests import tiny

P, H, V, T, L = 16384, 2048, 262272, 30, 20
ATTN = H * (1280 + 256) + 2 * 10 * 128 * 128 + 1024 * H
ROUTER = H * 256 + 2 * 256 * 256 + 256 * 17
EXPERT = 3 * H * 2048
PAIR = 2 * 8 * 2 * 128


@pytest.fixture(scope="module")
def config():
    return tiny.config_file("zaya1_8b_20l")


@pytest.fixture(scope="module")
def cost(config):
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost")


def test_the_parameter_count_is_model_init_s(config, cost):
    """The latent attention's matrices 5.57 M, the router's 0.66 M, 16
    experts x 12.58 M, 26,405 small ones a layer: 4,690,897,636 with the tied
    embedding once, what ``model.init`` declares at the published widths
    (tests/test_cca_moe.py counts the same tree) and at the tiny ones, and
    what the file states."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.config.config import ModelConfig
    from cst_captioning_tpu.models import CaptionModel

    m = config["model"]
    assert cost.attention_weights(m) == ATTN == 5_570_560
    assert cost.attention_weights(m, kv_only=True) == ATTN - 1024 * H
    assert cost.router_weights(m) == ROUTER == 659_712
    assert cost.expert_weights(m) == EXPERT == 12_582_912
    assert cost.parameter_count(m) == 4_690_897_636 == config["parameters"]["count"]
    assert config["parameters"]["bytes"] == 9_381_817_232
    for sizes in (m, config["tiny"]["model"]):
        mc = ModelConfig(**{k: tuple(map(tuple, v)) if k == "modalities" else v
                            for k, v in sizes.items()})
        model = CaptionModel(mc)
        width = sizes["modalities"][0][1]
        shapes = jax.eval_shape(lambda: model.init(
            jax.random.key(0), {"patch": jnp.zeros((1, 8, width))},
            {"patch": jnp.ones((1, 8))},
            jnp.zeros((1, sizes["max_len"]), jnp.int32)))
        leaves = jax.tree.leaves(shapes)
        assert cost.parameter_count(sizes) == sum(x.size for x in leaves)
    assert sum(x.size * x.dtype.itemsize for x in leaves) < 4 * 50_000  # tiny


def test_the_mechanism_cost_is_not_above_what_its_kernel_walks(config, cost):
    """The kernel walks whole tiles: for every query tile the key tiles up to
    the diagonal's, each a full ``tq x tk`` product; the cost model counts the
    pairs under the diagonal and no other, so a roofline share taken against
    it cannot pass 100 % by the count."""
    import numpy as np

    from cst_captioning_tpu.ops import window_attention as wa

    m = config["model"]
    mech = cost.mechanism_cost(m, {"B": 2})["cca_attn"]
    tq, tk = wa.CCA_TILES
    first, last, steps = wa._walk(P, tq, tk, None)
    qi = np.arange(P // tq)
    walked = int((np.asarray(last(qi)) - np.asarray(first(qi)) + 1).sum()) * tq * tk
    counted = mech["flops"] / (2 * (L - 1) * PAIR)
    assert counted == cost.prefix_pairs(P) == P * (P + 1) // 2
    assert counted <= walked <= 1.04 * counted
    assert steps * (P // tq) * tq * tk >= walked           # the grid's extent
    assert cost.pair_flops(m) == PAIR == 4096
    # 19 layers' pairs of two clips: 20.9 TFLOP; q, k, v read once and the
    # output written: 8 + 2 + 2 + 8 heads of 128 a position
    assert mech["flops"] == pytest.approx(20.9e12, rel=0.005)
    assert mech["bytes"] == 2 * (L - 1) * P * (2 * 1024 + 2 * 256) * 2
    assert costs.roofline(mech, "TPU v5 lite")[1] == "flops"


def test_a_batch_s_prefix_is_44_tflop_and_48_percent_of_it_the_pairs(config, cost):
    m = config["model"]
    shape = {"kind": "eval", "B": 2, "beam": 5}
    got = cost.program_cost(m, shape)
    token = 2 * (ATTN + ROUTER + EXPERT * 16 / 17)
    assert cost.layer_token_flops(m) == pytest.approx(token)
    assert token == pytest.approx(36.1e6, rel=0.005)
    prefill = 2 * (2 * P * 1024 * H + (L - 1) * (P * token + PAIR * P * (P + 1) // 2)
                   + P * 2 * (ATTN - 1024 * H))
    assert got["eval_prefill"]["flops"] == pytest.approx(prefill)
    assert prefill == pytest.approx(43.7e12, rel=0.01)
    pairs = cost.mechanism_cost(m, shape)["cca_attn"]["flops"]
    assert pairs / prefill == pytest.approx(0.48, abs=0.01)
    # a step: 10 lanes' tokens (the tied head, 20 layers, the pairs of one
    # query over 16384 + t + 1 keys)
    steps = sum(10 * (2 * H * V + L * (token + PAIR * (P + t + 1)))
                for t in range(T))
    assert got["eval_decode"]["flops"] == pytest.approx(steps)
    assert steps < 0.03 * prefill           # the search is bound by bytes


def test_a_step_reads_the_experts_its_lanes_reach_and_the_prefix_once_a_clip(
        config, cost):
    m = config["model"]
    assert cost.experts_reached(m, 10) == pytest.approx(16 * (1 - (16 / 17) ** 10))
    assert cost.experts_reached(m, 10) == pytest.approx(7.27, abs=0.01)
    assert cost.held_share(m) == 16 / 17
    shared = L * P * 2 * 256 * 2            # a clip's latent prefix: 335 MB
    assert shared == 335_544_320
    assert cost.tail_bytes(m) == (2 * 1280 + 128) * 2
    weights = 2 * (H * V + L * (ATTN + ROUTER + cost.experts_reached(m, 10) * EXPERT))
    assert cost.weight_bytes(m, rows=10) == pytest.approx(weights)
    full = cost.program_cost(m, {"kind": "eval", "B": 2, "beam": 5})["eval_decode"]
    one_step = weights + 2 * shared + 10 * (2 * L * 2 * 256 * 2) \
        + 10 * 2 * L * cost.tail_bytes(m) + 2 * 10 * V * 4
    assert full["bytes"] == pytest.approx(
        30 * one_step + 10 * L * 2 * 256 * 2 * sum(range(30)))
    assert one_step == pytest.approx(5.66e9, rel=0.01)
    # lanes that have ended cost nothing: half the lanes, one clip left
    half = dict(kind="eval", B=2, beam=5, profile={
        "lanes": [5.0] * T, "clips": [1.0] * T, "steps": [1.0] * T})
    assert cost.program_cost(m, half)["eval_decode"]["bytes"] < full["bytes"]
    none = dict(half, profile={k: [0.0] * T for k in ("lanes", "clips", "steps")})
    assert cost.program_cost(m, none)["eval_decode"] == {"flops": 0.0, "bytes": 0.0}


def test_it_is_costed_for_job_eval_alone(config, cost):
    with pytest.raises(ValueError, match="job eval alone"):
        cost.program_cost(config["model"], {"kind": "xe", "B": 2})
    with pytest.raises(ValueError, match="steps"):
        cost.program_cost(config["model"], {
            "kind": "eval", "B": 2, "beam": 5,
            "profile": {"lanes": [1.0], "clips": [1.0], "steps": [1.0]}})
