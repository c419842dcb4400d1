"""The reader ``eval_prefill_share`` (PR 38) on a stand-in reduction."""

import pytest

from benchmark.layer_metrics import eval_prefill_share as reader
from benchmark.tests import sala_reading


def test_it_is_the_prefill_program_s_share_of_the_two():
    assert reader.read(sala_reading.reading()) == pytest.approx(
        100.0 * 2.40 / (2.40 + 0.60))


def test_the_decode_program_is_the_one_the_job_names():
    r = sala_reading.reading()
    r["result"]["modules"]["eval_decode"] = r"^jit_other$"
    assert reader.read(r) == pytest.approx(100.0 * 2.40 / (2.40 + 9.0))


@pytest.mark.parametrize("modules", [
    {"jit__lambda": 3.0},                       # one program runs both
    {"jit_eval_prefill": 3.0},                  # no decode in the stretch
    {"jit_eval_prefill_of_another_name": 1.0, "jit__lambda": 1.0},
])
def test_nothing_to_read_is_none(modules):
    assert reader.read(sala_reading.reading(modules=modules)) is None


def test_an_untraced_run_or_a_job_without_the_program_reads_none():
    r = sala_reading.reading()
    assert reader.read(dict(r, trace=None)) is None
    r["result"]["modules"] = {}
    assert reader.read(r) is None
