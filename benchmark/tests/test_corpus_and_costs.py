"""The corpus generator is reproducible from its parameters; the cost models
(found through the configuration's file) and the peaks table say what they
claim."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import corpus, costs, training
from benchmark.tests import tiny

CONFIG = tiny.config_file("msrvtt_attention")
TINY_CORPUS = CONFIG["tiny"]["corpus"]


def _digest(paths):
    import h5py

    h = hashlib.sha256(open(paths["info_json"], "rb").read())
    for name in sorted(k for k in paths if k != "info_json"):
        with h5py.File(paths[name], "r") as f:
            for vid in sorted(f):
                h.update(np.asarray(f[vid]).tobytes())
    return h.hexdigest()


def test_corpus_is_a_function_of_its_parameters(tmp_path):
    params = TINY_CORPUS
    a = corpus.ensure_corpus(str(tmp_path / "a"), params)
    b = corpus.ensure_corpus(str(tmp_path / "b"), params)
    assert _digest(a) == _digest(b)
    c = corpus.ensure_corpus(str(tmp_path / "c"), dict(params, seed=8))
    assert _digest(c) != _digest(a)
    # reused, not rebuilt, on the second ask
    before = os.path.getmtime(a["info_json"])
    assert corpus.ensure_corpus(str(tmp_path / "a"), params) == a
    assert os.path.getmtime(a["info_json"]) == before


def test_corpus_schema_and_lengths(tmp_path):
    import h5py

    params = TINY_CORPUS
    paths = corpus.ensure_corpus(str(tmp_path), params)
    info = json.load(open(paths["info_json"]))
    assert info["vocab"][:4] == list(corpus.SPECIAL_TOKENS)
    assert len(info["vocab"]) == params["vocab_size"]
    assert len(info["videos"]) == params["videos"]
    lo, hi = params["caption_len"]
    for v in info["videos"]:
        assert v["split"] == "train" and len(v["captions"]) == params["refs_per_video"]
        for ids, raw in zip(v["caption_ids"], v["captions"]):
            assert lo <= len(ids) <= hi and len(raw.split()) == len(ids)
            assert min(ids) >= 4 and max(ids) < params["vocab_size"]
    with h5py.File(paths["resnet"], "r") as f:
        shapes = {f[v["id"]].shape for v in info["videos"]}
    assert {s[1] for s in shapes} == {32}
    assert all(params["min_frames"] <= s[0] <= params["max_frames"] for s in shapes)


MSRVTT = CONFIG["model"]


def test_costs_follow_the_stated_conventions():
    lstm = training.config_module(CONFIG, "costs", "program_cost")
    assert lstm.__file__.endswith("benchmark/cost_models/lstm_captioner.py")
    enc, tok = lstm.enc_and_per_tok_flops(MSRVTT)
    # 2mnk: frame embeddings + memory projection; attention, LSTM, softmax
    assert enc == 2 * 28 * 2548 * 512 + 2 * 56 * 512 * 256
    assert tok == (2 * 512 * 256 + 2 * 56 * 256 + 2 * 56 * 512
                   + 2 * 1024 * 2048 + 2 * 512 * 2048 + 2 * 512 * 9000)
    c = costs.program_cost(CONFIG, {"kind": "cst", "B": 1792, "K": 5, "chunks": 5})
    assert c["update"]["flops"] == 3 * c["decode"]["flops"]
    x = costs.program_cost(CONFIG, {"kind": "xe", "B": 64})["xe"]
    assert x["flops"] == 3 * 64 * (enc + 30 * tok)
    assert lstm.memory_slots(dict(MSRVTT, encoder="meanpool")) == 2


# what ``costs.program_cost(model, shape)`` returned before the arithmetic
# moved behind the configuration's ``costs`` module (PR 26's tree): the
# dispatch has to give the same numbers, to the last digit
OLD_COSTS = [
    ({"kind": "cst", "B": 1792, "K": 5, "chunks": 5},
     {"decode": {"flops": 4419213066240.0, "bytes": 25595273216},
      "update": {"flops": 13257639198720.0, "bytes": 143630573568.0}}),
    ({"kind": "cst", "B": 448, "K": 5, "chunks": 5},
     {"decode": {"flops": 1104803266560.0, "bytes": 7112757248},
      "update": {"flops": 3314409799680.0, "bytes": 46565044224.0}}),
    ({"kind": "xe", "B": 64},
     {"xe": {"flops": 108173721600.0, "bytes": 3837235200.0}}),
]


@pytest.mark.parametrize("shape, old", OLD_COSTS,
                         ids=["cst_b1792", "cst_b448", "xe_b64"])
def test_the_dispatch_returns_the_old_numbers(shape, old):
    assert costs.program_cost(CONFIG, shape) == old


def _tokens(lengths, K, T=30):
    """[1, K, B, T] sampled rows of the given lengths (EOS included), PAD
    after: lane ``k`` of clip ``b`` is ``lengths[k][b]`` long."""
    lengths = np.asarray(lengths).reshape(K, -1)
    return (np.arange(T)[None, None, :] < lengths[:, :, None]).astype(np.int32)[None] * 7


@pytest.mark.parametrize("shape, old", OLD_COSTS,
                         ids=["cst_b1792", "cst_b448", "xe_b64"])
def test_captions_of_full_length_cost_what_was_counted_before(shape, old):
    """The count of PR 34 sums over the tokens that ran; with every caption
    ``max_len`` long that is the old count, to the last digit: from the
    profile the cost model builds itself, and from one taken off tokens."""
    K = shape.get("K", 1)
    full = costs.caption_profile(_tokens([30] * (K * shape["B"]), K),
                                 shape.get("chunks", 1))
    assert costs.program_cost(CONFIG, dict(shape, profile=full)) == old
    four = costs.chip_share(dict(shape, B=4 * shape["B"], profile={
        k: v if k.endswith("steps") else [4 * x for x in v]
        for k, v in full.items()}), 4)
    assert costs.program_cost(CONFIG, four) == old


def test_the_count_is_monotone_in_the_profile_and_stops_at_the_longest():
    shape = {"kind": "cst", "B": 8, "K": 5, "chunks": 5}
    rng = np.random.default_rng(0)
    lengths = rng.integers(3, 19, size=(5, 8))
    short = costs.caption_profile(_tokens(lengths, 5), 5)
    longer = costs.caption_profile(_tokens(lengths + (lengths < 10), 5), 5)
    assert sum(short["steps"]) == lengths.max() and short["lanes"][0] == 40
    assert short["clips"][17] == (lengths.max(0) > 17).sum()
    assert short["chunk_clips"] == short["lanes"]     # one lane a slice
    assert short["chunk_steps"][17] == (lengths.max(1) > 17).sum()
    a, b, full = (costs.program_cost(CONFIG, dict(shape, profile=p))
                  for p in (short, longer, None))
    for program in ("decode", "update"):
        for key in ("flops", "bytes"):
            assert a[program][key] < b[program][key] < full[program][key]
    # nothing beyond the batch's longest caption: the same lanes in a model
    # that may write 40 tokens cost the same scan
    wide = dict(CONFIG, model=dict(MSRVTT, max_len=40))
    p40 = costs.caption_profile(_tokens(lengths, 5, T=40), 5)
    assert costs.program_cost(wide, dict(shape, profile=p40)) == a
    # one slice for all the lanes reads a clip's bank once a step
    one = costs.caption_profile(_tokens(lengths, 5), 1)
    assert one["chunk_clips"] == one["clips"] and one["chunk_steps"] == one["steps"]
    with pytest.raises(ValueError, match="profile has 40 steps"):
        costs.program_cost(CONFIG, dict(shape, profile=p40))


@pytest.mark.parametrize("program", ["decode", "update"])
def test_a_step_timed_at_the_counted_bytes_over_the_peak_reads_100(program):
    """The roofline reader on a toy step: device time = the counted bytes
    over the published bandwidth reads exactly 100 %, whatever the profile;
    and a program that skipped the padding cannot read more by it, because
    the padding was never counted."""
    from benchmark.layer_metrics import _common

    lengths = np.random.default_rng(1).integers(4, 20, size=(5, 1792))
    shape = {"kind": "cst", "B": 1792, "K": 5, "chunks": 5,
             "profile": costs.caption_profile(_tokens(lengths, 5), 5)}
    for chips in (1, 4):
        cost = costs.program_cost(CONFIG, costs.chip_share(shape, chips))[program]
        least, bound = costs.roofline(cost, "TPU v5 lite")
        assert bound == "hbm"
        reading = {"result": {"cost_shape": shape, "modules": {program: program}},
                   "trace": {"devices": [{"module_runs_s": {
                       "jit_" + program: [least] * 4}}] * chips},
                   "chips": chips, "config": CONFIG, "device_kind": "TPU v5 lite"}
        assert _common.roofline_share(reading, program) == pytest.approx(100.0)
        full = dict(shape, profile=None)
        assert _common.roofline_share(dict(reading, result=dict(
            reading["result"], cost_shape=full)), program) > 200.0


def test_the_eval_program_costs_a_beam_of_lanes():
    shape = {"kind": "eval", "B": 256, "beam": 5}
    c = costs.program_cost(CONFIG, shape)
    d = costs.program_cost(CONFIG, {"kind": "cst", "B": 256, "K": 5, "chunks": 5})
    assert c == {"eval_decode": d["decode"]}
    lengths = np.random.default_rng(2).integers(4, 20, size=(1, 256))
    tokens = np.repeat(_tokens(lengths, 1), 5, 1)
    short = costs.program_cost(CONFIG, dict(
        shape, profile=costs.caption_profile(tokens)))["eval_decode"]
    assert short["flops"] < c["eval_decode"]["flops"]
    assert short["bytes"] < c["eval_decode"]["bytes"]


def test_no_cost_model_is_an_error_never_a_default(tmp_path):
    nameless = {k: v for k, v in CONFIG.items() if k != "costs"}
    with pytest.raises(SystemExit, match="names no 'costs' module"):
        costs.program_cost(nameless, OLD_COSTS[0][0])
    with pytest.raises(SystemExit, match="not in this checkout"):
        costs.program_cost(dict(CONFIG, costs="benchmark/cost_models/none.py"),
                           OLD_COSTS[0][0])
    # a module that is there and has no program_cost: the reference, say
    with pytest.raises(SystemExit, match="has no function program_cost"):
        costs.program_cost(dict(CONFIG, costs=CONFIG["reference"]),
                           OLD_COSTS[0][0])
    with pytest.raises(SystemExit, match="has no function token_logprobs"):
        training.config_module(dict(CONFIG, reference=CONFIG["costs"]),
                               "reference", "token_logprobs")


def test_the_second_architecture_costs_by_its_own_module():
    second = tiny.second_architecture()
    c = costs.program_cost(second, {"kind": "cst", "B": 32, "K": 5, "chunks": 5})
    own = training.config_module(second, "costs", "program_cost")
    assert own.__file__.endswith("tests/second_architecture/costs.py")
    assert c == own.program_cost(second["model"],
                                 {"kind": "cst", "B": 32, "K": 5, "chunks": 5})
    assert set(c) == {"decode", "update"} and c["update"]["flops"] > 0


def test_a_tolerance_needs_a_value_and_a_reason():
    assert training.check_value(CONFIG, "logprob_mean_abs_tol") == 0.01
    assert training.check_value(CONFIG, "mesh_rel_tol") == 0.02
    assert training.check_value(CONFIG, "loss_abs_tol") == 0.005
    for broken in ({}, {"value": 1.0}, {"value": 1.0, "reason": ""}):
        with pytest.raises(SystemExit, match="states no checks.some_tol"):
            training.check_value(dict(CONFIG, checks={"some_tol": broken}),
                                 "some_tol")
    for name, entry in CONFIG["checks"].items():
        assert entry["reason"] and "value" in entry, name


def test_peaks_raise_for_an_unknown_kind():
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    least, bound = costs.roofline({"flops": 197e12, "bytes": 1.0}, "TPU v5 lite")
    assert (round(least, 9), bound) == (1.0, "flops")
    least, bound = costs.roofline({"flops": 1.0, "bytes": 819e9}, "TPU v5e")
    assert (round(least, 9), bound) == (1.0, "hbm")
