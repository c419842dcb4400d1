"""``correct`` has been shown to fail (``PERF.md`` section 4): the controls
kept as tests, at sizes a CPU walks. Each drives a whole run of the ``cst``
job and the harness's ``settle`` behind it, as ``run.py`` does after its look
for a chip.

- The precision control. A configuration that states float32 passes at its
  float32 tolerances; the same files with the program switched to bfloat16
  underneath, the nearest precision below, do not. So too with the reference
  itself, computed in bfloat16, put in the program's place.
- The timed path broken underneath: an ``update`` that returns its state
  unchanged, and one that leaves half the batch out, come out not correct by
  the number that is there to catch each.
"""

import importlib

import pytest

from benchmark import run as bench_run
from benchmark import training
from benchmark.tests import tiny

FOLLOWED = ("rl_loss_step1_abs_diff", "first_grad_worst_leaf_gap",
            "first_grad_rel_diff", "param_change_worst_leaf_gap")


def _configs():
    whole = tiny.config_file("msrvtt_attention")
    second = tiny.second_architecture()
    return [pytest.param((tiny.tiny_config(whole), tiny.tiny_workload(whole, "cst")),
                         id="msrvtt_attention"),
            pytest.param((second, tiny.tiny_workload(second, "cst")),
                         id="second_architecture")]


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("bench_cache")


def _run(config, workload, cache):
    ctx = tiny.Ctx(workload, config, cache)
    job = importlib.import_module("benchmark.jobs." + workload["job"])
    result = job.run(ctx)
    followed = result["followed"]
    return ctx, bench_run.settle(result, ctx.log), followed


@pytest.fixture(scope="module", params=_configs())
def sound(request, cache):
    config, workload = request.param
    assert config["model"]["dtype"] == "float32"
    return (config, workload) + _run(config, workload, cache)


def test_stated_float32_passes_at_float32_tolerances(sound):
    config, _workload, _ctx, res, _followed = sound
    assert res["correct"] and res["failed"] == 0, res["compared"]
    for name in FOLLOWED + ("decode_logprob_mean_abs_diff",):
        row = res["compared"][name]
        # float32 tolerances, each with its reason written in the file
        assert row["ok"] and row["limit"] <= 2e-4, (name, row)
    assert all(e["reason"] for e in config["checks"].values())


def test_bfloat16_program_under_a_file_that_states_float32_fails(
        sound, cache, monkeypatch):
    config, workload, _ctx, passed, _followed = sound
    stated = training.experiment_config

    def coarser(*args, **kw):
        # the file still states float32 (the sizes check has passed); the
        # program computes in bfloat16
        return stated(*args, **kw).override(model__dtype="bfloat16")

    monkeypatch.setattr(training, "experiment_config", coarser)
    _ctx, res, _ = _run(config, workload, cache)
    assert not res["correct"] and res["failed"] >= 2
    cmp = res["compared"]
    # the 64-clip log-probabilities and the new check on the window's own
    # update each refuse it, by three times their limit and more
    for name in ("decode_logprob_mean_abs_diff", "rl_loss_step1_abs_diff",
                 "first_grad_worst_leaf_gap", "first_grad_rel_diff"):
        assert not cmp[name]["ok"], (name, cmp[name])
        assert cmp[name]["value"] > 3 * cmp[name]["limit"]
        assert cmp[name]["value"] > 10 * passed["compared"][name]["value"]
    # and nothing that has no business with precision moved
    for name in ("sampled_len_mean", "reward_native_vs_python_max_abs",
                 "sampled_token_id_max", "trainer_scorer_native"):
        assert cmp[name]["ok"]


def test_the_reference_at_bfloat16_in_the_programs_place_fails(sound):
    _config, _workload, ctx, _res, followed = sound
    held = followed.control("bfloat16", ctx.log)
    assert held.failed
    for name in ("decode_logprob_mean_abs_diff", "first_grad_worst_leaf_gap",
                 "first_grad_rel_diff"):
        assert not held.rows[name]["ok"], held.rows[name]
        assert held.rows[name]["value"] > 3 * held.rows[name]["limit"]


def _break_update(monkeypatch, broken_call):
    """``make_rl_update`` as the program has it, undonated, with
    ``broken_call(real, state, feats, masks, samples, adv, valid)`` in its
    place: the fault sits under ``SCSTTrainer.update``, where the tap and the
    window find it."""
    from cst_captioning_tpu.rl import scst as scst_mod

    real_factory = scst_mod.make_rl_update

    def factory(model, **kw):
        real = real_factory(model, **dict(kw, donate=False))
        return lambda *args: broken_call(real, *args)

    monkeypatch.setattr(scst_mod, "make_rl_update", factory)


def test_an_update_that_returns_its_state_unchanged_is_not_correct(
        cache, monkeypatch):
    config, workload = _configs()[0].values[0]

    def unchanged(real, state, *batch):
        _new, metrics = real(state, *batch)
        return state, metrics

    _break_update(monkeypatch, unchanged)
    _ctx, res, _ = _run(config, workload, cache)
    cmp = res["compared"]
    assert not res["correct"]
    # the number that is there to catch it reads 1: the leaf has not moved
    assert cmp["param_change_worst_leaf_gap"]["value"] == pytest.approx(1.0)
    assert not cmp["param_change_worst_leaf_gap"]["ok"]
    assert not cmp["params_moved"]["ok"]
    # the loss at unchanged weights is the reference's first loss: sound
    assert cmp["rl_loss_step1_abs_diff"]["ok"]


def test_an_update_that_leaves_half_the_batch_out_is_not_correct(
        cache, monkeypatch):
    config, workload = _configs()[0].values[0]

    def half(real, state, feats, masks, samples, adv, valid):
        return real(state, feats, masks, samples, adv,
                    valid.at[: valid.shape[0] // 2].set(0.0))

    _break_update(monkeypatch, half)
    _ctx, res, _ = _run(config, workload, cache)
    cmp = res["compared"]
    assert not res["correct"]
    assert not cmp["rl_loss_step1_abs_diff"]["ok"]
    assert cmp["rl_loss_step1_abs_diff"]["value"] > \
        100 * cmp["rl_loss_step1_abs_diff"]["limit"]
    assert not cmp["first_grad_worst_leaf_gap"]["ok"]
