"""The reader ``cca_attn_roofline`` (PR 50) on a stand-in reduction."""

import pytest

from benchmark import costs
from benchmark.layer_metrics import cca_attn_roofline as reader
from benchmark.tests import cca_moe_reading
from benchmark.tests.cca_moe_reading import KERNEL_S
from benchmark.training import config_module


def test_it_is_the_cost_model_s_least_time_over_the_kernel_s():
    r = cca_moe_reading.reading()
    cost = config_module(r["config"], "costs", "mechanism_cost").mechanism_cost(
        r["config"]["model"], {"B": 2})["cca_attn"]
    least, bound = costs.roofline(cost, "TPU v5 lite")
    # 19 layers' 20.9 TFLOP of pairs against 3.2 GB of q, k, v and output
    assert bound == "flops"
    assert reader.read(r) == pytest.approx(100.0 * least / (KERNEL_S / 2))
    assert 0.0 < reader.read(r) < 100.0


def test_a_kernel_that_takes_twice_as_long_reads_half():
    slow = dict(cca_moe_reading.OPS)
    slow["cca_attn_prefill.1"] += KERNEL_S
    assert reader.read(cca_moe_reading.reading(ops=slow)) == pytest.approx(
        reader.read(cca_moe_reading.reading()) / 2)


def test_nothing_to_read_is_none():
    r = cca_moe_reading.reading(ops={"fusion.1": 1.0})
    assert reader.read(r) is None
    r = cca_moe_reading.reading()
    r["config"]["costs"] = "benchmark/cost_models/lstm_captioner.py"
    assert reader.read(r) is None       # a cost model without the function
