"""The reader ``sparse_attn_roofline`` (PR 38) on a stand-in reduction."""

import pytest

from benchmark import costs
from benchmark.layer_metrics import sparse_attn_roofline as reader
from benchmark.tests import sala_reading
from benchmark.training import config_module


def test_it_is_the_cost_model_s_least_time_over_the_kernel_s():
    r = sala_reading.reading()
    cost = config_module(r["config"], "costs", "mechanism_cost").mechanism_cost(
        r["config"]["model"], {"B": 2})["sparse_attn"]
    least, bound = costs.roofline(cost, "TPU v5 lite")
    assert bound == "flops"         # 2.7 TFLOP against 0.6 GB
    assert reader.read(r) == pytest.approx(100.0 * least / 0.045)
    # one sparse layer's prefix, two clips, the attended keys only: 13.6 ms
    assert 25.0 < reader.read(r) < 35.0


def test_a_kernel_that_walks_what_the_rule_masks_out_reads_lower():
    slow = dict(sala_reading.OPS, **{"sparse_attn_prefill.1": 0.180})
    assert reader.read(sala_reading.reading(ops=slow)) == pytest.approx(
        reader.read(sala_reading.reading()) / 2)


def test_nothing_to_read_is_none():
    r = sala_reading.reading(ops={"fusion.1": 1.0})
    assert reader.read(r) is None
    r = sala_reading.reading()
    r["config"]["costs"] = "benchmark/cost_models/lstm_captioner.py"
    assert reader.read(r) is None       # a cost model without the function
