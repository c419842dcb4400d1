"""The reader ``sparse_selected_key_share`` (PR 38) on a made-up obs stream."""

import json

import pytest

from benchmark.layer_metrics import sparse_selected_key_share as reader
from benchmark.tests import sala_reading


def _reading(tmp_path, snapshots):
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        for ts, counters in snapshots:
            f.write(json.dumps({"event": "metrics", "ts": ts,
                                "counters": counters}) + "\n")
    r = sala_reading.reading()
    return dict(r, obs_dir=str(obs), window=(10.0, 20.0), wall_minus_perf=0.0)


def test_it_is_the_window_s_growth_of_the_two_counters(tmp_path):
    r = _reading(tmp_path, [
        (5.0, {"sparse.keys_visible": 1000.0, "sparse.keys_selected": 900.0}),
        (9.0, {"sparse.keys_visible": 2000.0, "sparse.keys_selected": 1500.0}),
        (19.0, {"sparse.keys_visible": 6000.0, "sparse.keys_selected": 3500.0}),
        (25.0, {"sparse.keys_visible": 9000.0, "sparse.keys_selected": 9000.0}),
    ])
    assert reader.read(r) == pytest.approx(100.0 * 2000.0 / 4000.0)


def test_nothing_pruned_reads_100(tmp_path):
    r = _reading(tmp_path, [
        (9.0, {"sparse.keys_visible": 10.0, "sparse.keys_selected": 10.0}),
        (19.0, {"sparse.keys_visible": 50.0, "sparse.keys_selected": 50.0})])
    assert reader.read(r) == 100.0


def test_a_program_without_the_counters_reads_none(tmp_path):
    r = _reading(tmp_path, [(9.0, {"eval.batches": 1.0}),
                            (19.0, {"eval.batches": 5.0})])
    assert reader.read(r) is None
    assert reader.read(dict(r, obs_dir=str(tmp_path / "nowhere"))) is None
