"""The eight readers of the program's own spans, on a hand-made ``reading``:
spans on two threads, some crossing the window's ends, nested children, an
umbrella ``rl.epoch``. Times are seconds on the benchmark's clock; the window
is (10, 20) and holds four steps."""

import importlib

import pytest

MAIN, WORKER = "MainThread", "prefetch"


def _span(name, t0, t1, thread=MAIN):
    return {"name": name, "t0": t0, "t1": t1, "dur": t1 - t0, "thread": thread}


def _reading(spans=None, traced=True, steps=4, main=MAIN):
    if spans is None:
        spans = SPANS
    return {
        "spans": spans, "window": (10.0, 20.0),
        "trace_window": (11.0, 15.0) if traced else None,
        # the job hands over the thread it drove the program's loop on
        "result": {"steps": [(12.0 + i, 32.0, 0.0, 0) for i in range(steps)],
                   "main_thread": main},
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


MAIN_THREAD_READERS = ("prefetch_wait_ms_per_step",
                       "host_unattributed_ms_per_step")


@pytest.mark.parametrize("name", MAIN_THREAD_READERS)
def test_main_thread_is_the_one_the_job_hands_over(name):
    """The readers name no span of a job to find the loop's thread by (up to
    PR 33 it was ``rl.reward``'s, and every other job read nothing): renamed
    threads are followed through ``main_thread``, a loop that records no
    ``rl.reward`` reads as well, and a job that hands no thread over has no
    main thread to read."""
    swap = {MAIN: "trainer-0", WORKER: "stager"}
    spans = [dict(s, thread=swap.get(s["thread"], s["thread"])) for s in SPANS]
    assert _read(name, _reading(spans, main="trainer-0")) == \
        pytest.approx(WANT[name])
    assert _read(name, _reading(spans)) != pytest.approx(WANT[name])
    no_reward = [s for s in SPANS if not s["name"].startswith("rl.reward")]
    got = _read(name, _reading(no_reward))
    if name == "prefetch_wait_ms_per_step":
        assert got == pytest.approx(WANT[name])
    else:   # the reward's second and a half are no longer named
        assert got == pytest.approx(WANT[name] + 1e3 * (1.0 + 0.8) / 4)
    assert _read(name, _reading(main=None)) is None


def test_an_eval_pass_reads_under_its_own_umbrella():
    """Job ``eval``'s spans: the umbrella ``eval`` encloses a pass and names
    nothing; ``eval.score`` is the corpus scorers at the pass's drain."""
    spans = [_span("eval", 9.0, 14.0), _span("data.collate", 10.0, 10.5),
             _span("data.collate", 11.0, 11.5),
             _span("eval.pipeline.drain", 12.0, 14.0),
             _span("eval.score", 12.5, 14.0), _span("eval", 14.0, 21.0),
             _span("data.collate", 14.5, 15.5),
             _span("eval.score", 19.5, 20.5)]       # crosses the end
    r = _reading(spans)
    assert _read("eval_score_ms_per_step", r) == pytest.approx(1e3 * 1.5 / 4)
    assert _read("collate_ms_per_step", r) == pytest.approx(1e3 * 2.0 / 4)
    assert _read("host_unattributed_ms_per_step", r) == pytest.approx(
        1e3 * (10.0 - (0.5 + 0.5 + 2.0 + 1.0 + 0.5)) / 4)
    assert _read("eval_score_ms_per_step", _reading(SPANS)) is None
    assert _read("reward_score_ms_per_step", r) is None


@pytest.mark.parametrize("grown", ["as_committed", "with_both"])
def test_the_eight_are_in_the_manifest_as_span_metrics(grown):
    """Each says where it exists: the readers of spans that every job's loop
    records carry no list; those of the RL step's, the epoch turnover's and
    the prefetch feed's spans (a job that decodes without a feed has none)
    list the two ``cst`` cells the benchmark was accepted with. Read off the
    manifest as committed and as later PRs' cells would leave it: no cell
    that is added is in a list that was there."""
    from benchmark.tests import tiny

    manifest = tiny.GROWN[grown](tiny.manifest())
    entries = {m["name"]: m for m in manifest["per_layer"]}
    for name in WANT:
        m = entries[name]
        assert (m["source"], m["unit"], m["better"], m["moves"]) == (
            "program_span", "ms", "lower", "clips_per_s_per_chip")
        if name in ("collate_ms_per_step", "host_unattributed_ms_per_step"):
            assert "workloads" not in m
        else:
            assert set(tiny.ACCEPTED_CST) <= set(m["workloads"]), name
