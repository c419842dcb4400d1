"""The reader ``window_attn_ms_per_step`` (PR 45) on a stand-in reduction."""

import pytest

from benchmark.layer_metrics import window_attn_ms_per_step as reader
from benchmark.tests import window_moe_reading

# the kernel's operations' seconds in the stand-in stretch of two steps
KERNEL_S = {"window": 8 * 0.010, "full": 2 * 0.150}["window"]


def test_it_sums_the_kernel_s_operations_over_the_stretch_s_steps():
    # the operations whose names only begin like the kernel's, and the other
    # kernel's, are not its
    assert reader.read(window_moe_reading.reading()) == pytest.approx(
        1e3 * KERNEL_S / 2)
    assert reader.read(window_moe_reading.reading(steps=1)) == pytest.approx(
        1e3 * KERNEL_S)


def test_nothing_to_read_is_none():
    other = {"window": "full", "full": "window"}["window"]
    r = window_moe_reading.reading(
        ops={"fusion.1": 1.0, other + "_attn_prefill.1": 2.0})
    assert reader.read(r) is None           # the parent: no such kernel
    assert reader.read(dict(window_moe_reading.reading(), trace=None)) is None
    assert reader.read(dict(window_moe_reading.reading(),
                            trace_window=None)) is None
