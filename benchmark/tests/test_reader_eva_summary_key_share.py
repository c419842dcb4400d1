"""The reader ``eva_summary_key_share`` (PR 42) on a made-up obs stream."""

import json

import pytest

from benchmark.layer_metrics import eva_summary_key_share as reader
from benchmark.tests import eva_reading


def _reading(tmp_path, snapshots):
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        for ts, counters in snapshots:
            f.write(json.dumps({"event": "metrics", "ts": ts,
                                "counters": counters}) + "\n")
    r = eva_reading.reading()
    return dict(r, obs_dir=str(obs), window=(10.0, 20.0), wall_minus_perf=0.0)


def test_it_is_the_window_s_growth_of_the_two_counters(tmp_path):
    r = _reading(tmp_path, [
        (5.0, {"eva.keys_exact": 1000.0, "eva.keys_summary": 100.0}),
        (9.0, {"eva.keys_exact": 2000.0, "eva.keys_summary": 500.0}),
        (19.0, {"eva.keys_exact": 5000.0, "eva.keys_summary": 1500.0}),
        (25.0, {"eva.keys_exact": 9000.0, "eva.keys_summary": 9000.0}),
    ])
    assert reader.read(r) == pytest.approx(100.0 * 1000.0 / 4000.0)


def test_a_prefix_inside_one_window_reads_0(tmp_path):
    r = _reading(tmp_path, [
        (9.0, {"eva.keys_exact": 10.0, "eva.keys_summary": 0.0}),
        (19.0, {"eva.keys_exact": 50.0, "eva.keys_summary": 0.0})])
    assert reader.read(r) == 0.0


def test_a_program_without_the_counters_reads_none(tmp_path):
    r = _reading(tmp_path, [(9.0, {"eval.batches": 1.0}),
                            (19.0, {"eval.batches": 5.0})])
    assert reader.read(r) is None
    assert reader.read(dict(r, obs_dir=str(tmp_path / "nowhere"))) is None
