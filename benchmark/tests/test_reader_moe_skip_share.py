"""The reader ``moe_skip_share`` (PR 50) on a made-up obs stream."""

import json

import pytest

from benchmark.layer_metrics import moe_skip_share as reader
from benchmark.tests import cca_moe_reading


def _reading(tmp_path, snapshots):
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        for ts, counters in snapshots:
            f.write(json.dumps({"event": "metrics", "ts": ts,
                                "counters": counters}) + "\n")
    r = cca_moe_reading.reading()
    return dict(r, obs_dir=str(obs), window=(10.0, 20.0), wall_minus_perf=0.0)


def _moe(every, skipped):
    return {"moe.assignments": every, "moe.assignments.skipped": skipped}


def test_it_is_the_window_s_growth_of_the_two_counters(tmp_path):
    r = _reading(tmp_path, [(5.0, _moe(100.0, 50.0)), (9.0, _moe(1700.0, 100.0)),
                            (19.0, _moe(5100.0, 300.0)),
                            (25.0, _moe(9000.0, 9000.0))])
    assert reader.read(r) == pytest.approx(100.0 * 200.0 / 3400.0)


def test_a_router_that_never_chose_none_reads_zero(tmp_path):
    r = _reading(tmp_path, [(9.0, _moe(10.0, 0.0)), (19.0, _moe(110.0, 0.0))])
    assert reader.read(r) == 0.0


def test_a_program_without_the_counter_reads_none(tmp_path):
    r = _reading(tmp_path, [(9.0, {"moe.assignments": 1.0}),
                            (19.0, {"moe.assignments": 5.0})])
    assert reader.read(r) is None       # the other expert kinds, the parent
    assert reader.read(dict(r, obs_dir=str(tmp_path / "nowhere"))) is None
