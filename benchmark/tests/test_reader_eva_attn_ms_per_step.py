"""The reader ``eva_attn_ms_per_step`` (PR 42) on a stand-in reduction."""

import pytest

from benchmark.layer_metrics import eva_attn_ms_per_step as reader
from benchmark.tests import eva_reading


def test_it_sums_the_kernel_s_operations_over_the_stretch_s_steps():
    # seven operations of 30 ms over two steps; the operations whose names
    # only begin like the kernel's are not its
    assert reader.read(eva_reading.reading()) == pytest.approx(1e3 * 0.21 / 2)
    assert reader.read(eva_reading.reading(steps=1)) == pytest.approx(1e3 * 0.21)


def test_nothing_to_read_is_none():
    r = eva_reading.reading(ops={"fusion.1": 1.0, "sparse_attn_prefill.1": 2.0})
    assert reader.read(r) is None           # the parent: no such kernel
    assert reader.read(dict(eva_reading.reading(), trace=None)) is None
    assert reader.read(dict(eva_reading.reading(), trace_window=None)) is None
