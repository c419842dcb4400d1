"""Main-thread milliseconds per step that no span of the program names: the
window's length less the union of the main thread's spans (clipped to the
window; the umbrellas ``rl.epoch`` / ``xe.epoch`` / ``eval`` / ``setup``, which only
enclose other work, left out), over the steps completed in the window. What
it holds is loop bookkeeping, log lines and whatever still lacks a span."""

from benchmark import trace_reduce
from benchmark.layer_metrics import _spans


def read(reading):
    steps = reading["result"].get("steps")
    main = _spans.main_threads(reading)
    mine = [s for s in reading["spans"]
            if s["thread"] in main and s["name"] not in _spans.UMBRELLAS]
    if reading["trace_window"] is None or not steps or not mine:
        return None     # no stretch, or a loop that records no span at all
    w0, w1 = reading["window"]
    named = trace_reduce.union_seconds(
        (1e9 * max(s["t0"], w0), 1e9 * min(s["t1"], w1))
        for s in mine if s["t1"] > w0 and s["t0"] < w1)
    return 1e3 * ((w1 - w0) - named) / len(steps)
