"""The reader ``linear_attn_roofline`` (PR 38) on a stand-in reduction."""

import pytest

from benchmark import costs
from benchmark.layer_metrics import linear_attn_roofline as reader
from benchmark.tests import sala_reading
from benchmark.training import config_module


def test_it_is_the_recurrence_s_least_time_over_the_kernels():
    r = sala_reading.reading()
    cost = config_module(r["config"], "costs", "mechanism_cost").mechanism_cost(
        r["config"]["model"], {"B": 2})["linear_attn"]
    # six layers: q, k, v read and the output written, 2 x 16384 x 4096 each
    assert cost["bytes"] == 6 * (2 * 16384 * 4 * 4096 * 2 + 2 * 32 * 128 * 128 * 4)
    assert cost["flops"] == 6 * 2 * 16384 * 32 * 4 * 128 * 128
    least, bound = costs.roofline(cost, "TPU v5 lite")
    assert bound == "hbm"
    assert reader.read(r) == pytest.approx(100.0 * least / 0.035)
    assert 0.0 < reader.read(r) < 100.0


def test_nothing_to_read_is_none():
    assert reader.read(sala_reading.reading(ops={})) is None
    assert reader.read(dict(sala_reading.reading(), trace=None)) is None
