"""The program's counters, gauges and histograms for readers that need them:
``reading["spans"]`` holds spans only, so these readers open the run's obs
stream themselves (``<run_dir>/obs/events.jsonl``: the ``metrics`` snapshots
the program writes, one at every evaluation pass's end; ``run.py`` keeps the
stream under ``benchmark/.cache/run/<cell>/obs``). The registry is the
process's and only grows, so a window's count is the difference between the
last snapshot at or before the window's close and the last at or before its
opening (job ``eval`` opens and closes its window on a pass's end, just
behind that pass's snapshot). A program without the counter (this PR's
parent), a run without obs or a stream without snapshots reads None."""

from __future__ import annotations

import json
import os
import time

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def obs_dir(reading) -> str:
    """Where the run's obs stream lies: ``reading["obs_dir"]`` where a test
    says so, else where ``run.Run`` points the program's recorder."""
    w = reading["workload"]
    return reading.get("obs_dir") or os.path.join(
        _BENCH, ".cache", "run", f"{w['config']}.{w['traffic']}", "obs")


def snapshots(reading) -> list[dict]:
    """The stream's ``metrics`` events in order, ``ts`` on the benchmark's
    ``perf_counter`` clock."""
    path = os.path.join(obs_dir(reading), "events.jsonl")
    if not os.path.exists(path):
        return []
    events = []
    with open(path) as f:
        for line in f:
            try:
                events.append(json.loads(line))
            except ValueError:
                continue
    # wall clock -> the benchmark's clock: both are this process's, so the
    # offset is what ``run.py`` took at its start (a test that feeds a
    # recorded stream says what it was)
    offset = reading.get("wall_minus_perf")
    if offset is None:
        offset = time.time() - time.perf_counter()
    return [dict(ev, ts=ev["ts"] - offset) for ev in events
            if ev.get("event") == "metrics"]


def window_pair(reading):
    """(the last snapshot at or before the window's opening, the last at or
    before its close), or None where the window holds no whole pass's
    snapshot."""
    t0, t1 = reading["window"]
    snaps = snapshots(reading)
    before = [s for s in snaps if s["ts"] <= t0]
    inside = [s for s in snaps if s["ts"] <= t1]
    if not before or not inside or inside[-1] is before[-1]:
        return None
    return before[-1], inside[-1]


def window_count(reading, name: str):
    """The counter ``name``'s growth over the window, or None."""
    pair = window_pair(reading)
    if pair is None or name not in pair[1].get("counters", {}):
        return None
    first, last = pair
    return last["counters"][name] - first.get("counters", {}).get(name, 0.0)
