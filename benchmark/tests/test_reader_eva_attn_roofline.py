"""The reader ``eva_attn_roofline`` (PR 42) on a stand-in reduction."""

import pytest

from benchmark import costs
from benchmark.layer_metrics import eva_attn_roofline as reader
from benchmark.tests import eva_reading
from benchmark.training import config_module


def test_it_is_the_cost_model_s_least_time_over_the_kernel_s():
    r = eva_reading.reading()
    cost = config_module(r["config"], "costs", "mechanism_cost").mechanism_cost(
        r["config"]["model"], {"B": 2})["eva_attn"]
    least, bound = costs.roofline(cost, "TPU v5 lite")
    assert bound == "flops"         # 5.4 TFLOP against 4 GB
    assert reader.read(r) == pytest.approx(100.0 * least / 0.105)
    # seven layers' pairs over two clips of 16384 positions: 27 ms
    assert 20.0 < reader.read(r) < 32.0


def test_a_kernel_that_walks_tiles_no_query_sees_reads_lower():
    slow = dict(eva_reading.OPS, **{"eva_attn_prefill.1": 0.240})
    assert reader.read(eva_reading.reading(ops=slow)) == pytest.approx(
        reader.read(eva_reading.reading()) / 2)


def test_nothing_to_read_is_none():
    r = eva_reading.reading(ops={"fusion.1": 1.0})
    assert reader.read(r) is None
    r = eva_reading.reading()
    r["config"]["costs"] = "benchmark/cost_models/lstm_captioner.py"
    assert reader.read(r) is None       # a cost model without the function
