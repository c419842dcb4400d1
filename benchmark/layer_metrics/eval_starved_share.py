"""The share of the window in which the evaluation loop had no decode
launched and uncollected, %: the growth of the program's counter
``eval.starved_seconds`` (from the collect that leaves the device with
nothing queued to the next launch: a pass's drain, scoring and snapshot, the
caller's turn, the next pass's first collate and upload) between the
snapshots at the window's two pass ends, over the window's seconds. The
program settles the stretch under way before it takes a pass's snapshot, so
the growth between two snapshots is the starved time between them. Read over
the whole window, so it sees the turnover of a pass longer than the traced
stretch, which ``device_idle_share`` cannot."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    starved = window_count(reading, "eval.starved_seconds")
    if starved is None:
        return None
    t0, t1 = reading["window"]
    return 100.0 * starved / (t1 - t0)
