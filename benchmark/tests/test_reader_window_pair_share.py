"""The reader ``window_pair_share`` (PR 45) on a made-up obs stream."""

import json

import pytest

from benchmark.layer_metrics import window_pair_share as reader
from benchmark.tests import window_moe_reading


def _reading(tmp_path, snapshots):
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        for ts, counters in snapshots:
            f.write(json.dumps({"event": "metrics", "ts": ts,
                                "counters": counters}) + "\n")
    r = window_moe_reading.reading()
    return dict(r, obs_dir=str(obs), window=(10.0, 20.0), wall_minus_perf=0.0)


def _pairs(near, whole, every):
    return {"attn.pairs_window": near, "attn.pairs_full": whole,
            "attn.pairs_causal": every}


def test_it_is_the_window_s_growth_of_the_three_counters(tmp_path):
    r = _reading(tmp_path, [
        (5.0, _pairs(10.0, 100.0, 550.0)),
        (9.0, _pairs(20.0, 200.0, 1100.0)),
        (19.0, _pairs(50.0, 600.0, 3300.0)),
        (25.0, _pairs(9000.0, 9000.0, 9000.0)),
    ])
    assert reader.read(r) == pytest.approx(100.0 * (30.0 + 400.0) / 2200.0)


def test_window_layers_that_attend_densely_read_100(tmp_path):
    r = _reading(tmp_path, [(9.0, _pairs(90.0, 20.0, 110.0)),
                            (19.0, _pairs(990.0, 220.0, 1210.0))])
    assert reader.read(r) == pytest.approx(100.0)


def test_a_program_without_the_counters_reads_none(tmp_path):
    r = _reading(tmp_path, [(9.0, {"eval.batches": 1.0}),
                            (19.0, {"eval.batches": 5.0})])
    assert reader.read(r) is None
    assert reader.read(dict(r, obs_dir=str(tmp_path / "nowhere"))) is None
