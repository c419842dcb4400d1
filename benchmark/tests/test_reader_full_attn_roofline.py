"""The reader ``full_attn_roofline`` (PR 45) on a stand-in reduction."""

import pytest

from benchmark import costs
from benchmark.layer_metrics import full_attn_roofline as reader
from benchmark.tests import window_moe_reading
from benchmark.training import config_module

KERNEL_S = {"window": 8 * 0.010, "full": 2 * 0.150}["full"]


def test_it_is_the_cost_model_s_least_time_over_the_kernel_s():
    r = window_moe_reading.reading()
    cost = config_module(r["config"], "costs", "mechanism_cost").mechanism_cost(
        r["config"]["model"], {"B": 2})["full_attn"]
    least, bound = costs.roofline(cost, "TPU v5 lite")
    # the full layers' 22 TFLOP against 2.9 GB; the band's 1.37 TFLOP against
    # 12 GB of q, k, v and output: a band of 128 keys is bound by the memory
    assert bound == {"window": "hbm", "full": "flops"}["full"]
    assert reader.read(r) == pytest.approx(100.0 * least / (KERNEL_S / 2))
    assert 0.0 < reader.read(r) < 100.0


def test_a_kernel_that_walks_tiles_no_query_sees_reads_lower():
    name = "full_attn_prefill.1"
    slow = dict(window_moe_reading.OPS)
    slow[name] = slow[name] + KERNEL_S
    assert reader.read(window_moe_reading.reading(ops=slow)) == pytest.approx(
        reader.read(window_moe_reading.reading()) / 2)


def test_nothing_to_read_is_none():
    r = window_moe_reading.reading(ops={"fusion.1": 1.0})
    assert reader.read(r) is None
    r = window_moe_reading.reading()
    r["config"]["costs"] = "benchmark/cost_models/lstm_captioner.py"
    assert reader.read(r) is None       # a cost model without the function
