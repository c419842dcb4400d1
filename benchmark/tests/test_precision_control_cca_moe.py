"""The precision control of ``configs/zaya1_8b_20l.json``, kept as a test
at the file's tiny sizes, where it states float32: a whole run of job ``eval``
and the harness's ``settle`` behind it come out correct at the float32 limits;
the configuration's reference computed at a lower precision (bfloat16, the
nearest below; fp8-e4m3, the full-size cell's control) put in the program's
place does not. The tiny model holds a share of its experts (4 of 8) and
its router's ninth output chooses none, so the share code and the no-expert
rows both run; a caption's first position reaches back into the prefix for
its two convolutions and its value's late half. The steadiest of the four
numbers is the mean distance between log-probabilities: both controls fail it
on every seed; a lower precision moves captions only where likely tokens
nearly tie, so the other three can pass by chance at bfloat16 on a seed (on
this one its captions are the float32 reference's own)."""

import importlib

import pytest

from benchmark import run as bench_run
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    whole = tiny.config_file("zaya1_8b_20l")
    config = tiny.tiny_config(whole)
    workload = tiny.tiny_workload(
        whole, "eval", tiny.workload_file("zaya1_8b_20l.eval_beam5_p16k"))
    assert config["model"]["dtype"] == config["model"]["param_dtype"] == "float32"
    ctx = tiny.Ctx(workload, config, tmp_path_factory.mktemp("bench_cache"))
    result = importlib.import_module("benchmark.jobs.eval").run(ctx)
    emitted = result["emitted"]
    return config, bench_run.settle(result, ctx.log), emitted


def test_stated_float32_passes_at_its_float32_limits(sound):
    config, res, _emitted = sound
    assert res["correct"] and res["failed"] == 0, res["compared"]
    cmp = res["compared"]
    for number, limit in (
            ("eval_beam_token_mismatch_share", "beam_token_mismatch_tol"),
            ("eval_beam_score_gap_mean", "beam_score_gap_tol"),
            ("eval_beam_rank_gap_max", "beam_rank_gap_tol"),
            ("eval_logprob_mean_abs_diff", "beam_logprob_mean_abs_tol")):
        assert cmp[number]["limit"] == config["checks"][limit]["value"]
    # the timed decode's captions are the reference's own, token for token
    assert cmp["eval_beam_token_mismatch_share"]["value"] == 0.0
    assert cmp["eval_beam_score_gap_mean"]["value"] < 5e-6
    assert cmp["eval_logprob_mean_abs_diff"]["value"] < 2e-6


@pytest.mark.parametrize("precision", ["bfloat16", "float8_e4m3fn"])
def test_the_reference_at_a_lower_precision_in_the_programs_place_fails(
        sound, precision):
    _config, res, emitted = sound
    held = emitted.control(precision)
    rows, cmp = held.rows, res["compared"]
    failed = set(held.failed)
    assert "eval_logprob_mean_abs_diff" in failed
    assert rows["eval_logprob_mean_abs_diff"]["value"] > \
        100 * rows["eval_logprob_mean_abs_diff"]["limit"]
    assert rows["eval_logprob_mean_abs_diff"]["value"] > \
        1000 * cmp["eval_logprob_mean_abs_diff"]["value"]
    if precision == "float8_e4m3fn":
        assert {"eval_beam_score_gap_mean",
                "eval_beam_token_mismatch_share"} <= failed
        assert rows["eval_beam_token_mismatch_share"]["value"] > 0.05
