"""The three readers of the evaluation loop's own spans (PR 40),
``eval_h2d_ms_per_step``, ``eval_launch_ms_per_step`` and
``eval_collect_wait_ms_per_step``, each on the same hand-made reading: one
call of ``_spans.ms_per_step`` with another span's name."""

import pytest

from benchmark.layer_metrics import (
    eval_collect_wait_ms_per_step, eval_h2d_ms_per_step,
    eval_launch_ms_per_step,
)
from benchmark.tests import eval_loop_reading as made

READERS = pytest.mark.parametrize("reader, span", [
    (eval_h2d_ms_per_step, "eval.h2d"),
    (eval_launch_ms_per_step, "eval.launch"),
    (eval_collect_wait_ms_per_step, "eval.collect"),
], ids=["h2d", "launch", "collect_wait"])


@READERS
def test_it_is_the_main_thread_s_spans_in_the_window_over_its_steps(reader, span):
    """Spans that cross the window's ends, lie outside it or belong to
    another thread's loop are not counted."""
    assert reader.read(made.reading()) == pytest.approx(made.WANT_MS[span])


@READERS
def test_a_span_on_another_thread_is_not_counted(reader, span):
    pool_only = [s for s in made.SPANS
                 if s["name"] != span or s["thread"] == made.POOL]
    assert reader.read(made.reading(pool_only)) == 0.0
    # the loop's thread is the one the job hands over, whatever its name
    swap = {made.MAIN: made.POOL, made.POOL: made.MAIN}
    swapped = [dict(s, thread=swap[s["thread"]]) for s in made.SPANS]
    assert reader.read(made.reading(swapped, main=made.POOL)) == \
        pytest.approx(made.WANT_MS[span])
    assert reader.read(made.reading(main=None)) is None


@READERS
def test_an_empty_window_reads_zero(reader, span):
    outside = [s for s in made.SPANS
               if not made.WINDOW[0] <= s["t0"] <= s["t1"] <= made.WINDOW[1]]
    assert reader.read(made.reading(outside)) == 0.0


@READERS
def test_a_parent_shaped_stream_or_an_untraced_run_reads_none(reader, span):
    assert reader.read(made.reading(made.parent_shaped())) is None
    assert reader.read(made.reading([])) is None
    assert reader.read(made.reading(traced=False)) is None
