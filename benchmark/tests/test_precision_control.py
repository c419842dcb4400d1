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
- Job ``eval`` (no cell lists it yet): the reference in bfloat16 in the
  program's place fails the log-probabilities of the emitted captions (at a
  64-word vocabulary no precision moves a caption, so the numbers read off
  the timed tokens stay where they are: the chip's readings, ``PERF.md``
  section 5, are where a precision moves them); and the timed path broken
  underneath fails the numbers read off the timed tokens: a token altered
  where it is produced, a greedy search where the beam should be, the
  worst-ranked hypothesis handed back for the best.
"""

import importlib

import pytest

from benchmark import run as bench_run
from benchmark import training
from benchmark.tests import tiny

FOLLOWED = ("rl_loss_step1_abs_diff", "first_grad_worst_leaf_gap",
            "first_grad_rel_diff", "param_change_worst_leaf_gap")


def _configs(job="cst"):
    whole = tiny.config_file("msrvtt_attention")
    second = tiny.second_architecture()
    return [pytest.param((tiny.tiny_config(whole), tiny.tiny_workload(whole, job)),
                         id="msrvtt_attention"),
            pytest.param((second, tiny.tiny_workload(second, job)),
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


# ---- job ``eval`` -------------------------------------------------------------


def _run_eval(config, workload, cache):
    ctx = tiny.Ctx(workload, config, cache)
    result = importlib.import_module("benchmark.jobs.eval").run(ctx)
    emitted = result["emitted"]
    return bench_run.settle(result, ctx.log), emitted


@pytest.fixture(scope="module", params=_configs("eval"))
def sound_eval(request, cache):
    config, workload = request.param
    assert config["model"]["dtype"] == "float32"
    return (config,) + _run_eval(config, workload, cache)


TIMED = ("eval_beam_token_mismatch_share", "eval_beam_score_gap_mean",
         "eval_beam_rank_gap_max")


def test_eval_stated_float32_passes_and_the_bfloat16_reference_in_its_place_fails(
        sound_eval):
    config, res, emitted = sound_eval
    assert res["correct"] and res["failed"] == 0, res["compared"]
    cmp = res["compared"]
    row = cmp["eval_logprob_mean_abs_diff"]
    assert row["limit"] == config["checks"]["beam_logprob_mean_abs_tol"]["value"]
    # float32 as stated: the timed decode's captions are the reference's own
    assert cmp["eval_beam_token_mismatch_share"]["value"] == 0.0
    assert cmp["eval_beam_score_gap_mean"]["value"] < 1e-6
    held = emitted.control("bfloat16")
    low = held.rows["eval_logprob_mean_abs_diff"]
    assert held.failed and not low["ok"]
    assert low["value"] > 3 * low["limit"] and low["value"] > 100 * row["value"]
    assert set(TIMED) <= set(held.rows)


def _break_beam(monkeypatch, broken):
    """The Evaluator's beam search with ``broken(real, *args, **kw)`` in its
    place: the fault sits under the compiled decode the window drives."""
    from cst_captioning_tpu.eval import evaluator

    real = evaluator.beam_search
    monkeypatch.setattr(evaluator, "beam_search",
                        lambda *args, **kw: broken(real, *args, **kw))


def test_a_token_altered_where_it_is_produced_is_not_correct(cache, monkeypatch):
    """The timed path broken underneath: the Evaluator's beam search hands
    back its captions with the second token changed. The program's step and
    the reference still agree on what those tokens are worth; the numbers
    read off the timed tokens do not: no beam of that width can have kept
    them, they are not the reference's captions, and they score nats lower."""
    import jax.numpy as jnp

    config, workload = _configs("eval")[0].values[0]
    V = config["model"]["vocab_size"]

    def altered(real, *args, **kw):
        tokens, scores = real(*args, **kw)
        second = tokens[:, 1]
        other = 4 + (second - 4 + (V - 4) // 2) % (V - 4)
        return tokens.at[:, 1].set(jnp.where(second >= 4, other, second)), scores

    _break_beam(monkeypatch, altered)
    res, _emitted = _run_eval(config, workload, cache)
    cmp = res["compared"]
    assert not res["correct"]
    for name in TIMED:
        assert not cmp[name]["ok"], (name, cmp[name])
        assert cmp[name]["value"] > 10 * cmp[name]["limit"]
    assert cmp["eval_beam_rank_gap_max"]["value"] > 1.0     # whole nats
    assert cmp["eval_beam_token_mismatch_share"]["value"] > 0.15
    assert cmp["eval_logprob_mean_abs_diff"]["ok"]
    assert cmp["eval_token_id_max"]["ok"]


def test_the_worst_ranked_hypothesis_for_the_best_is_not_correct(
        cache, monkeypatch):
    """What a one-sided gap under the beam's edge cannot see: a final ranking
    that hands back the worst of the beam's hypotheses for the best. Every
    token of such a caption is one a beam of that width keeps; the captions
    are not the reference's, and the reference scores them under its own.
    (A greedy search in the beam's place is the same kind of fault; at these
    sizes it emits the beam's captions, so the chip reads it: ``PERF.md``
    section 5.)"""
    config, workload = _configs("eval")[0].values[0]

    def worst_ranked(real, *args, **kw):
        tokens, scores = real(*args, **dict(kw, return_all=True))
        return tokens[:, -1], scores[:, -1]

    _break_beam(monkeypatch, worst_ranked)
    res, _emitted = _run_eval(config, workload, cache)
    cmp = res["compared"]
    assert not res["correct"]
    for name in ("eval_beam_token_mismatch_share", "eval_beam_score_gap_mean"):
        assert not cmp[name]["ok"], (name, cmp[name])
        assert cmp[name]["value"] > 10 * cmp[name]["limit"]
    assert cmp["eval_logprob_mean_abs_diff"]["ok"]
    print("worst_ranked", {k: cmp[k]["value"] for k in TIMED})
