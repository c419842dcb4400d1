"""The precision control of ``configs/kimi_k2_ep32.json``, kept as a test at
the file's tiny sizes, where it states float32: a whole run of job ``eval``
and the harness's ``settle`` behind it come out correct at the float32
limits; the configuration's reference computed in bfloat16, the nearest
precision below, put in the program's place does not. With seeded weights
near-ties flip under rounding, so here (unlike ``msrvtt_attention``'s trained
policy, ``test_precision_control.py``) the control also moves captions: it
fails the numbers read off the emitted tokens as well as the
log-probabilities; only the one-sided gap under the beam's edge cannot see it
(a caption the lower precision prefers is still made of likely tokens)."""

import importlib

import pytest

from benchmark import run as bench_run
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    whole = tiny.config_file("kimi_k2_ep32")
    config = tiny.tiny_config(whole)
    workload = tiny.tiny_workload(whole, "eval",
                                  tiny.workload_file("kimi_k2_ep32.eval_beam5"))
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
    assert cmp["eval_logprob_mean_abs_diff"]["value"] < 5e-6


@pytest.mark.parametrize("precision", ["bfloat16", "float8_e4m3fn"])
def test_the_reference_at_a_lower_precision_in_the_programs_place_fails(
        sound, precision):
    _config, res, emitted = sound
    held = emitted.control(precision)
    rows, cmp = held.rows, res["compared"]
    failed = set(held.failed)
    assert {"eval_logprob_mean_abs_diff", "eval_beam_token_mismatch_share",
            "eval_beam_score_gap_mean"} <= failed
    for name in failed:
        assert rows[name]["value"] > 3 * rows[name]["limit"], (name, rows[name])
    assert rows["eval_logprob_mean_abs_diff"]["value"] > \
        1000 * cmp["eval_logprob_mean_abs_diff"]["value"]
    # bfloat16 keeps every emitted token inside the float32 beam's edge;
    # fp8 does not
    assert ("eval_beam_rank_gap_max" in failed) == (precision != "bfloat16")
