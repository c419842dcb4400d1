"""The reader ``cca_attn_ms_per_step`` (PR 50) on a stand-in reduction."""

import pytest

from benchmark.layer_metrics import cca_attn_ms_per_step as reader
from benchmark.tests import cca_moe_reading
from benchmark.tests.cca_moe_reading import KERNEL_S


def test_it_sums_the_kernel_s_operations_over_the_stretch_s_steps():
    # the operations whose names only begin like the kernel's, and another
    # kind's kernel, are not its
    assert reader.read(cca_moe_reading.reading()) == pytest.approx(
        1e3 * KERNEL_S / 2)
    assert reader.read(cca_moe_reading.reading(steps=1)) == pytest.approx(
        1e3 * KERNEL_S)
    # a program that unrolled its layers would name one operation a layer
    split = {k: v for k, v in cca_moe_reading.OPS.items()
             if k != "cca_attn_prefill.1"}
    split.update({"cca_attn_prefill": KERNEL_S / 4,
                  **{f"cca_attn_prefill.{i}": KERNEL_S / 4 for i in (2, 5, 9)}})
    assert reader.read(cca_moe_reading.reading(ops=split)) == pytest.approx(
        1e3 * KERNEL_S / 2)


def test_nothing_to_read_is_none():
    r = cca_moe_reading.reading(ops={"fusion.1": 1.0, "full_attn_prefill.1": 2.0})
    assert reader.read(r) is None           # the parent: no such kernel
    assert reader.read(dict(cca_moe_reading.reading(), trace=None)) is None
    assert reader.read(dict(cca_moe_reading.reading(), trace_window=None)) is None
