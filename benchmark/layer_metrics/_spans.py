"""What the readers of the program's own spans share (``_common.py`` is
frozen, so it lives here). ``reading["spans"]`` holds every span the program
recorded, on the benchmark's clock, with ``name``, ``t0``, ``t1``, ``dur`` and
``thread`` and nothing else: nesting is by time and thread.

The rules, the same in every such reader: nothing to read (None) in a run
whose profiler stretch never happened (``trace_window`` None) and in a
program that records no span of the name at all; otherwise a number, 0.0
where the window holds none. A span counts if it lies wholly inside the
window."""

from __future__ import annotations

from benchmark.layer_metrics._common import window_spans

# spans that only enclose other work: they say nothing about where time went
UMBRELLAS = ("rl.epoch", "xe.epoch", "eval", "setup")


def main_threads(reading) -> set[str]:
    """The thread that runs the job's loop, as the job hands it over
    (``main_thread`` in its result: the name of the thread it drove the
    program's loop on, which is the one its loop timer and its step
    submissions run on). No job of the program is named here: a job that
    hands none over has no main thread to read."""
    name = reading["result"].get("main_thread")
    return {name} if name else set()


def _inside(reading, name: str, threads=None):
    """Spans ``name`` wholly inside the window (on ``threads`` only, if
    given), or None where there is nothing to read."""
    if reading["trace_window"] is None:
        return None
    if threads is not None and not threads:
        return None     # no loop to be the main thread of
    if not any(s["name"] == name for s in reading["spans"]):
        return None
    return [s for s in window_spans(reading, (name,))
            if threads is None or s["thread"] in threads]


def ms_per_step(reading, name: str, threads=None):
    """Summed milliseconds of the spans ``name`` in the window, over the
    steps completed in it."""
    inside = _inside(reading, name, threads)
    steps = reading["result"].get("steps")
    if inside is None or not steps:
        return None
    return 1e3 * sum(s["dur"] for s in inside) / len(steps)


def ms_mean(reading, name: str):
    """Mean milliseconds of one span ``name`` in the window."""
    inside = _inside(reading, name)
    if inside is None:
        return None
    return 1e3 * sum(s["dur"] for s in inside) / len(inside) if inside else 0.0
