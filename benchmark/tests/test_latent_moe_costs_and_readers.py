"""What PR 36 brought under ``benchmark/``: the latent-attention decoder's
cost model (``cost_models/latent_moe_decoder.py``) and the three readers of
the routed-expert decode's counters (``moe_local_assignment_share``,
``moe_expert_rows_max_over_mean``, ``eval_decode_cache_gib``), which open the
run's obs stream themselves. The rehearsal feeds a committed cell's line only
to the readers that were on disk before (``tiny.READERS``), so these are fed
here: a recorded stream (``recorded_obs_eval.json``: the metrics snapshots of
one CPU rehearsal of the cell at its tiny sizes), and streams that hold
nothing to read, as the parent of PR 36 leaves them."""

import json
import os

import pytest

from benchmark import costs
from benchmark.layer_metrics import (_counters, eval_decode_cache_gib,
                                     moe_expert_rows_max_over_mean,
                                     moe_local_assignment_share)
from benchmark.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kimi_k2_ep32.eval_beam5"


@pytest.fixture(scope="module")
def config():
    return tiny.config_file("kimi_k2_ep32")


@pytest.fixture(scope="module")
def cost(config):
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost")


# ---- the cost model -------------------------------------------------------------


def test_the_weights_a_step_reads_are_the_chips_share(config, cost):
    """ISSUE 36's arithmetic: attention 101.1 M a layer, an expert 44.04 M, a
    router 2.75 M, the dense layer 497.5 M with its attention, an expert layer
    676.4 M, the head 146.8 M: 4.7 B parameters a step reads, 9.4 GB."""
    m = config["model"]
    attn = (7168 * 1536 + 1536 * 12288 + 7168 * 576 + 512 * 16384
            + 8192 * 7168)
    assert cost.attention_weights(m) == attn == 101_122_048
    assert cost.dense_ffn_weights(m) == 3 * 7168 * 18432
    assert cost.expert_ffn_weights(m) == 7168 * 384 + 13 * 3 * 7168 * 2048
    per_step = cost.stack_weight_bytes(m)
    assert per_step == 2 * (7 * attn + 3 * 7168 * 18432
                            + 6 * (7168 * 384 + 13 * 3 * 7168 * 2048)
                            + 7168 * 20480)
    assert 9.3e9 < per_step < 9.5e9
    assert cost.held_share(m) == 0.25 and cost.n_prefix(m) == 56


def test_every_caption_30_long_equals_the_closed_form(config, cost):
    m = config["model"]
    B, W, T, P, L = 256, 5, 30, 56, 7
    got = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W})
    assert set(got) == {"eval_decode"}
    flops = B * cost.prefill_clip_flops(m) + B * W * sum(
        cost.step_token_flops(m, P + t + 1) for t in range(T))
    row = 576 * 2
    nbytes = (cost.stack_weight_bytes(m, prefill=True)
              + B * 28 * 2548 * 4 + B * L * P * row
              + T * cost.stack_weight_bytes(m)
              + B * W * L * row * sum(P + t + 2 for t in range(T))
              + T * 2 * B * W * 20480 * 4)
    assert got["eval_decode"]["flops"] == pytest.approx(flops, rel=1e-12)
    assert got["eval_decode"]["bytes"] == pytest.approx(nbytes, rel=1e-12)
    # a step of 1280 lanes: about 4.2 TFLOP; a batch: FLOP-bound on a v5e
    step = B * W * cost.step_token_flops(m, P + 15)
    assert 4.0e12 < step < 4.4e12
    least, bound = costs.roofline(got["eval_decode"], "TPU v5 lite")
    assert bound == "flops" and 0.7 < least < 1.0
    # the same count from a profile that says so
    full = cost.full_profile(T, B, B * W)
    again = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W,
                                        "profile": full})
    assert again == got


def test_no_step_past_the_longest_caption_costs_anything(config, cost):
    m = config["model"]
    B, W, T = 256, 5, 30
    short = {"lanes": [float(B * W)] * 10 + [0.0] * 20,
             "clips": [float(B)] * 10 + [0.0] * 20,
             "steps": [1.0] * 10 + [0.0] * 20}
    got = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W,
                                      "profile": short})["eval_decode"]
    ten = B * cost.prefill_clip_flops(m) + B * W * sum(
        cost.step_token_flops(m, 56 + t + 1) for t in range(10))
    assert got["flops"] == pytest.approx(ten, rel=1e-12)
    whole = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W})
    assert got["bytes"] < whole["eval_decode"]["bytes"] \
        - 19 * cost.stack_weight_bytes(m)
    # half the lanes at a step: half that step's token work, the weights whole
    half = dict(short, lanes=[float(B * W)] * 9 + [B * W / 2] + [0.0] * 20)
    less = costs.program_cost(config, {"kind": "eval", "B": B, "beam": W,
                                       "profile": half})["eval_decode"]
    assert got["flops"] - less["flops"] == pytest.approx(
        B * W / 2 * cost.step_token_flops(m, 56 + 10), rel=1e-9)
    assert got["bytes"] - less["bytes"] < 0.1 * cost.stack_weight_bytes(m)


def test_the_cost_model_says_which_job_it_knows(config):
    with pytest.raises(ValueError, match="job eval alone"):
        costs.program_cost(config, {"kind": "xe", "B": 8})
    with pytest.raises(ValueError, match="steps"):
        costs.program_cost(config, {"kind": "eval", "B": 8, "beam": 5,
                                    "profile": {"lanes": [1.0], "clips": [1.0],
                                                "steps": [1.0]}})


# ---- the readers of the program's counters -----------------------------------


@pytest.fixture()
def recorded(tmp_path):
    """The recorded stream laid out as a run leaves it, and the reading the
    harness would hand a reader of the cell."""
    with open(os.path.join(HERE, "recorded_obs_eval.json")) as f:
        rec = json.load(f)
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        f.write("not json\n")       # a torn line is skipped, as read_spans does
        for ev in rec["events"]:
            f.write(json.dumps(ev) + "\n")
    reading = {"workload": tiny.workload_file(CELL), "obs_dir": str(obs),
               "window": tuple(rec["window"]),
               "wall_minus_perf": rec["wall_minus_perf"], "spans": []}
    return rec, reading


def test_the_three_readers_read_the_recorded_stream(recorded):
    rec, reading = recorded
    first, last = _counters.window_pair(reading)
    assert first["ts"] <= reading["window"][0] < last["ts"] <= reading["window"][1]
    every = last["counters"]["moe.assignments"] - first["counters"]["moe.assignments"]
    local = (last["counters"]["moe.assignments.local"]
             - first["counters"]["moe.assignments.local"])
    assert every > local > 0
    share = moe_local_assignment_share.read(reading)
    assert share == pytest.approx(100.0 * local / every)
    # the tiny cell holds 4 of 16 experts: a quarter under uniform routing
    assert 15.0 < share < 40.0
    ratio = moe_expert_rows_max_over_mean.read(reading)
    h0, h1 = (s["histograms"]["moe.expert_rows"] for s in (first, last))
    assert ratio == pytest.approx(
        h1["max"] / ((h1["sum"] - h0["sum"]) / (h1["count"] - h0["count"])))
    assert ratio > 1.0
    # one observation a decoded batch for each of 2 x 4 held experts
    assert h1["count"] - h0["count"] == 8 * rec["steps_in_window"]
    gib = eval_decode_cache_gib.read(reading)
    # 32 clips x 5 beams x 3 layers x 28 positions x 24 numbers x 4 B, and the
    # position and the tally beside them
    assert gib * 2**30 == last["gauges"]["decode.cache_bytes"]
    assert 32 * 5 * 3 * 28 * 24 * 4 <= gib * 2**30 < 1.01 * 32 * 5 * 3 * 28 * 24 * 4


def test_the_default_place_is_where_run_py_points_the_recorder(recorded):
    _rec, reading = recorded
    del reading["obs_dir"]
    assert _counters.obs_dir(reading).endswith(
        os.path.join("benchmark", ".cache", "run", CELL, "obs"))


@pytest.mark.parametrize("stream", ["no_stream", "no_snapshots", "no_counters",
                                    "window_before_any_snapshot"])
def test_nothing_to_read_is_none_and_never_raises(recorded, tmp_path, stream):
    """The parent of PR 36 has no such counter, an untraced run no stream: a
    reader returns None, and the line leaves the metric out."""
    rec, reading = recorded
    path = os.path.join(reading["obs_dir"], "events.jsonl")
    if stream == "no_stream":
        os.remove(path)
    elif stream == "no_snapshots":
        with open(path, "w") as f:
            f.write(json.dumps({"event": "span", "name": "eval", "ts": 1.0,
                                "dur": 0.5}) + "\n")
    elif stream == "no_counters":
        with open(path, "w") as f:
            for ev in rec["events"]:
                f.write(json.dumps(dict(ev, counters={"eval.batches": 1.0},
                                        gauges={}, histograms={})) + "\n")
    else:
        reading["window"] = (0.0, 1.0)
    for reader in (moe_local_assignment_share, moe_expert_rows_max_over_mean,
                   eval_decode_cache_gib):
        assert reader.read(reading) is None
