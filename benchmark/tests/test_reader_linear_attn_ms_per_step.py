"""The reader ``linear_attn_ms_per_step`` (PR 38) on a stand-in reduction."""

import pytest

from benchmark.layer_metrics import linear_attn_ms_per_step as reader
from benchmark.tests import sala_reading


def test_it_sums_every_linear_layer_s_kernel_over_the_stretch_s_steps():
    r = sala_reading.reading()
    assert reader.read(r) == pytest.approx(1e3 * (0.020 + 0.022 + 0.018 + 0.010) / 2)


def test_the_mean_over_devices_and_the_steps_of_the_stretch_alone():
    r = sala_reading.reading(steps=4)
    second = dict(r["trace"]["devices"][0], device=1,
                  op_self_s={"linear_attn_prefill.6": 0.030})
    r["trace"]["devices"].append(second)
    assert reader.read(r) == pytest.approx(1e3 * (0.070 + 0.030) / 2 / 4)


def test_nothing_to_read_is_none():
    r = sala_reading.reading(ops={"fusion.1": 1.0})
    assert reader.read(r) is None
    assert reader.read(dict(r, trace=None)) is None
