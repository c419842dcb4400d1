"""A stand-in for what the harness hands a reader after a traced run of a
cell of job ``eval``, for the readers of the evaluation loop's own spans and
counter (PR 40): the program's spans of two passes of two batches on the
benchmark's clock (the window is (10, 20) and holds four steps), the loop's
thread as the job hands it over, a worker pool's thread beside it, and an obs
stream of pass-end snapshots. Times are made up; nothing here is a
measurement."""

from __future__ import annotations

import json

MAIN, POOL = "MainThread", "ThreadPoolExecutor-0_0"
WINDOW = (10.0, 20.0)


def span(name, t0, t1, thread=MAIN):
    return {"name": name, "t0": t0, "t1": t1, "dur": t1 - t0, "thread": thread}


def _batch(t, collect_s):
    """One batch's collate, upload and launch from ``t``, then the collect of
    the batch before it."""
    return [span("data.collate", t, t + 0.40), span("eval.h2d", t + 0.40, t + 0.45),
            span("eval.launch", t + 0.45, t + 0.50),
            span("eval.collect", t + 0.50, t + 0.50 + collect_s)]


SPANS = [
    span("setup", 0.0, 8.0),
    # the pass that ends where the window opens: its last collect crosses in
    span("eval", 8.0, 10.0), span("eval.collect", 9.0, 10.0 + 1e-3),
    # two passes inside the window
    span("eval", 10.1, 14.9), *_batch(10.2, 0.0)[:3], *_batch(10.8, 1.5),
    span("eval.collect", 12.9, 14.3), span("eval.pipeline.drain", 14.3, 14.8),
    span("eval", 15.0, 19.9), *_batch(15.1, 0.0)[:3], *_batch(15.7, 1.6),
    span("eval.collect", 17.9, 19.2), span("eval.pipeline.drain", 19.2, 19.8),
    # after the window, and somebody else's loop on another thread
    *_batch(20.5, 1.0),
    span("eval.collect", 11.0, 13.0, POOL), span("eval.h2d", 11.0, 11.5, POOL),
    span("eval.launch", 12.0, 12.5, POOL),
]
NEW = ("eval.h2d", "eval.launch", "eval.collect")
# per step, over the window's four: two collects a pass wholly inside it
WANT_MS = {"eval.collect": 1e3 * (1.5 + 1.4 + 1.6 + 1.3) / 4,
           "eval.h2d": 1e3 * 4 * 0.05 / 4, "eval.launch": 1e3 * 4 * 0.05 / 4}


def reading(spans=None, traced=True, main=MAIN, **more):
    return {
        "spans": SPANS if spans is None else spans, "window": WINDOW,
        "trace_window": (11.0, 15.0) if traced else None,
        "workload": {"config": "a_config", "traffic": "eval_beam5"},
        "result": {"steps": [(t, 32.0, 0.0, 0) for t in (12.3, 14.3, 17.3, 19.2)],
                   "main_thread": main},
        **more,
    }


def parent_shaped(spans=None):
    """The stream of a program that records none of the new spans."""
    return [s for s in (SPANS if spans is None else spans)
            if s["name"] not in NEW]


def with_snapshots(tmp_path, snapshots):
    """A reading whose obs stream holds ``snapshots``: (seconds on the
    benchmark's clock, the counters then), one a pass's end."""
    obs = tmp_path / "obs"
    obs.mkdir()
    with open(obs / "events.jsonl", "w") as f:
        for ts, counters in snapshots:
            f.write(json.dumps({"event": "metrics", "ts": ts,
                                "counters": counters}) + "\n")
    return reading(obs_dir=str(obs), wall_minus_perf=0.0)
