"""The reader ``eval_starved_share`` (PR 40) on a made-up obs stream."""

import pytest

from benchmark.layer_metrics import eval_starved_share as reader
from benchmark.tests import eval_loop_reading as made

NAME = "eval.starved_seconds"


def test_it_is_the_counter_s_growth_over_the_window_s_seconds(tmp_path):
    """Between the last snapshot at or before the window's opening and the
    last at or before its close; the window is 10 s."""
    r = made.with_snapshots(tmp_path, [
        (5.0, {NAME: 0.0}), (9.9, {NAME: 0.4}), (14.9, {NAME: 0.85}),
        (19.9, {NAME: 1.3}), (24.9, {NAME: 9.0})])
    assert reader.read(r) == pytest.approx(100.0 * (1.3 - 0.4) / 10.0)


def test_a_window_without_a_turnover_reads_zero(tmp_path):
    r = made.with_snapshots(tmp_path, [(9.9, {NAME: 0.4}), (19.9, {NAME: 0.4})])
    assert reader.read(r) == 0.0


def test_a_counter_that_came_to_be_inside_the_window_counts_from_zero(tmp_path):
    r = made.with_snapshots(tmp_path, [(9.9, {"eval.batches": 2.0}),
                                       (19.9, {"eval.batches": 6.0, NAME: 0.5})])
    assert reader.read(r) == pytest.approx(5.0)


def test_a_parent_shaped_stream_reads_none(tmp_path):
    """A program without the counter, a window without a whole pass's
    snapshot, a run without an obs stream."""
    r = made.with_snapshots(tmp_path, [(9.9, {"eval.batches": 2.0}),
                                       (19.9, {"eval.batches": 6.0})])
    assert reader.read(r) is None
    assert reader.read(dict(r, window=(30.0, 40.0))) is None
    assert reader.read(dict(r, obs_dir=str(tmp_path / "nowhere"))) is None
