"""The reader ``sparse_attn_ms_per_step`` (PR 38) on a stand-in reduction."""

from benchmark.layer_metrics import sparse_attn_ms_per_step as reader
from benchmark.tests import sala_reading


def test_it_sums_the_kernels_operations_over_the_stretch_s_steps():
    r = sala_reading.reading()
    assert reader.read(r) == 1e3 * 0.090 / 2


def test_operations_that_only_begin_with_the_name_are_not_the_kernel():
    ops = {k: v for k, v in sala_reading.OPS.items()
           if not k.startswith("sparse_attn_prefill.")}
    assert reader.read(sala_reading.reading(ops=ops)) is None


def test_a_program_without_the_kernel_or_an_untraced_run_reads_none():
    r = sala_reading.reading()
    assert reader.read(dict(r, trace=None)) is None
    assert reader.read(dict(r, trace_window=None)) is None
    r["result"]["steps"] = []
    assert reader.read(r) is None
