"""The eight readers of the program's own spans, on a hand-made ``reading``:
spans on two threads, some crossing the window's ends, nested children, an
umbrella ``rl.epoch``. Times are seconds on the benchmark's clock; the window
is (10, 20) and holds four steps."""

import importlib

import pytest

MAIN, WORKER = "MainThread", "prefetch"


def _span(name, t0, t1, thread=MAIN):
    return {"name": name, "t0": t0, "t1": t1, "dur": t1 - t0, "thread": thread}


def _reading(spans=None, traced=True, steps=4):
    if spans is None:
        spans = SPANS
    return {
        "spans": spans, "window": (10.0, 20.0),
        "trace_window": (11.0, 15.0) if traced else None,
        "result": {"steps": [(12.0 + i, 32.0, 0.0, 0) for i in range(steps)]},
    }


SPANS = [
    # ---- main thread ----
    _span("setup", 0.0, 9.0),
    _span("rl.epoch.keys", 9.0, 9.5),            # before the window
    _span("rl.epoch", 9.5, 14.0),                # umbrella, crosses the start
    _span("prefetch.wait", 9.8, 10.2),           # crosses the start: not counted
    _span("rl.decode", 10.2, 10.5),
    _span("prefetch.wait", 10.5, 11.5),
    _span("rl.reward", 11.5, 12.5),
    _span("rl.reward.readback", 11.5, 11.9),
    _span("rl.reward.observe", 11.9, 12.0),
    _span("rl.reward.score", 12.0, 12.4),
    _span("rl.update", 12.5, 12.6),
    _span("prefetch.wait", 12.6, 13.0),          # the end marker's get
    _span("rl.epoch.drain", 13.0, 13.8),
    _span("obs.snapshot", 14.1, 14.2),
    _span("ckpt", 14.2, 15.0),
    _span("ckpt.readback", 14.2, 14.8),
    _span("rl.epoch.keys", 15.0, 15.1),
    _span("rl.epoch", 15.2, 21.0),               # umbrella, crosses the end
    _span("prefetch.wait", 15.2, 16.2),
    _span("rl.reward", 16.2, 17.0),
    _span("rl.reward.score", 16.4, 17.0),
    _span("rl.epoch.drain", 19.0, 20.5),         # crosses the end
    _span("rl.epoch.keys", 21.0, 21.3),          # after the window
    # ---- the prefetch worker ----
    _span("prefetch.stage", 9.0, 10.4, WORKER),
    _span("data.collate", 9.1, 10.1, WORKER),    # crosses the start
    _span("prefetch.h2d", 10.1, 10.4, WORKER),
    _span("prefetch.stage", 10.4, 11.6, WORKER),
    _span("data.collate", 10.4, 11.4, WORKER),
    _span("prefetch.h2d", 11.4, 11.6, WORKER),
    _span("prefetch.stage", 15.2, 16.3, WORKER),
    _span("data.collate", 15.3, 16.1, WORKER),
    _span("prefetch.h2d", 16.1, 16.3, WORKER),
    # a wait of somebody else's loop (an evaluator's prefetch) is not the
    # training loop's
    _span("prefetch.wait", 17.0, 19.0, "eval-thread"),
]

WANT = {
    "collate_ms_per_step": 1e3 * (1.0 + 0.8) / 4,
    "h2d_ms_per_step": 1e3 * (0.3 + 0.2 + 0.2) / 4,
    "prefetch_wait_ms_per_step": 1e3 * (1.0 + 0.4 + 1.0) / 4,
    "reward_score_ms_per_step": 1e3 * (0.4 + 0.6) / 4,
    "epoch_drain_ms": 1e3 * 0.8,
    "epoch_readback_ms": 1e3 * 0.6,
    "epoch_keys_ms": 1e3 * 0.1,
    # the main thread's named time in (10, 20): 10.0-13.8 without a hole
    # (the crossing wait clipped), 14.1-15.1, 15.2-17.0, 19.0-20.0
    "host_unattributed_ms_per_step":
        1e3 * (10.0 - (3.8 + 1.0 + 1.8 + 1.0)) / 4,
}


def _read(name, reading):
    return importlib.import_module("benchmark.layer_metrics." + name).read(reading)


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_arithmetic(name):
    assert _read(name, _reading()) == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_nothing_to_read_without_a_traced_stretch(name):
    assert _read(name, _reading(traced=False)) is None


@pytest.mark.parametrize("name", sorted(WANT))
def test_a_program_without_the_spans_gives_nothing_and_does_not_raise(name):
    """The parent commit records rl.decode / rl.reward / rl.update / ckpt
    only: the new spans' readers find nothing; the unattributed remainder
    is computed from what there is, and is large."""
    old = {"setup", "rl.epoch", "rl.decode", "rl.reward", "rl.update", "ckpt",
           "prefetch.stage"}
    got = _read(name, _reading([s for s in SPANS if s["name"] in old]))
    if name == "host_unattributed_ms_per_step":
        assert got == pytest.approx(1e3 * (10.0 - (0.3 + 1.0 + 0.1 + 0.8 + 0.8)) / 4)
    else:
        assert got is None
    assert _read(name, _reading([])) is None


def test_per_epoch_readers_give_zero_where_the_window_holds_none():
    only_outside = [s for s in SPANS if not 10.0 <= s["t0"] <= s["t1"] <= 20.0]
    for name in ("epoch_drain_ms", "epoch_readback_ms", "epoch_keys_ms"):
        got = _read(name, _reading(only_outside))
        assert got == (None if name == "epoch_readback_ms" else 0.0)


def test_main_thread_is_the_reward_spans_thread():
    """Renamed threads: the readers follow rl.reward, not a thread's name."""
    swap = {MAIN: "trainer-0", WORKER: "stager"}
    spans = [dict(s, thread=swap.get(s["thread"], s["thread"])) for s in SPANS]
    for name in ("prefetch_wait_ms_per_step", "host_unattributed_ms_per_step"):
        assert _read(name, _reading(spans)) == pytest.approx(WANT[name])
    no_loop = [s for s in SPANS if s["name"] != "rl.reward"]
    for name in ("prefetch_wait_ms_per_step", "host_unattributed_ms_per_step"):
        assert _read(name, _reading(no_loop)) is None


def test_the_eight_are_in_the_manifest_as_span_metrics_of_every_cell():
    import json
    import os

    from benchmark import run as bench_run

    with open(os.path.join(bench_run.ROOT, "BENCHMARK.json")) as f:
        entries = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in WANT:
        m = entries[name]
        assert (m["source"], m["unit"], m["better"], m["moves"]) == (
            "program_span", "ms", "lower", "clips_per_s_per_chip")
        assert "workloads" not in m
