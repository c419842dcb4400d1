"""The traced stretch (``run.Stretch``) on the CPU: the profiler replaced by a
recording fake, step completions fed by hand. What is checked is which
interval the stretch reads and when it stops; no trace is taken."""

import contextlib
import signal
import threading
import time
import types

import pytest

from benchmark import run as bench_run
from benchmark import training


class FakeProfiler:
    """``jax.profiler``'s three names the stretch uses, recording calls."""

    def __init__(self):
        self.calls: list = []

    class ProfileOptions:
        pass

    def start_trace(self, log_dir, profiler_options=None):
        self.calls.append(("start", log_dir, vars(profiler_options)))

    def stop_trace(self):
        self.calls.append(("stop", time.perf_counter()))

    @contextlib.contextmanager
    def TraceAnnotation(self, name):
        self.calls.append(("annotation", name))
        yield


def _wait_for(cond, timeout=5.0):
    deadline = time.perf_counter() + timeout
    while not cond():
        assert time.perf_counter() < deadline, "timed out"
        time.sleep(0.001)


def _stretch(tmp_path, period, **kw):
    prof = FakeProfiler()
    return bench_run.Stretch(str(tmp_path), period, profiler=prof, **kw), prof


def _join(st):
    st._thread.join(5.0)
    assert not st._thread.is_alive()


@pytest.mark.parametrize("delay_s", [0.0, 0.013, 0.027, 0.041])
def test_one_period_of_steps_whatever_the_phase(tmp_path, delay_s):
    """Steps complete every 10 ms, 4 an epoch; the trace starts at some
    phase of an epoch. The stretch is (the first completion after the mark,
    the 4th after that), and the trace stops there."""
    st, prof = _stretch(tmp_path, 4, delay_s=delay_s, cap_s=5.0)
    fed: list[float] = []
    st.start()
    while st._thread.is_alive():
        fed.append(time.perf_counter())
        st.step_completed(fed[-1])
        time.sleep(0.01)
    _join(st)
    assert st.error is None and st.steps == st.period == 4
    after = [t for t in fed if t > st.sync_mark_perf]
    assert st.window == (after[0], after[4])
    assert sum(st.window[0] < t <= st.window[1] for t in fed) == 4
    kinds = [c[0] for c in prof.calls]
    assert kinds == ["start", "annotation", "stop"]
    assert prof.calls[0][2] == bench_run.PROFILE_OPTIONS
    assert prof.calls[1] == ("annotation", "bench.sync")
    # stopped at the period's end, not at the cap, not a step later
    assert prof.calls[2][1] - after[4] < 0.01
    assert st.stop_trace_s is not None


def test_the_cap_cuts_a_stretch_whose_period_does_not_come(tmp_path, capsys):
    st, prof = _stretch(tmp_path, 4, delay_s=0.0, cap_s=0.05)
    st.start()
    _wait_for(lambda: st.sync_mark_perf is not None)
    fed = [time.perf_counter() + 0.001 * k for k in range(3)]
    for t in fed:
        st.step_completed(t)
    _join(st)
    assert st.error is None
    assert st.window == (fed[0], fed[2]) and st.steps == 2   # whole steps
    stop = prof.calls[-1]
    assert stop[0] == "stop" and 0.04 < stop[1] - fed[0] < 0.5
    assert "CUT by its cap" in capsys.readouterr().err


def test_the_cap_with_no_step_at_all_reads_from_the_mark(tmp_path, capsys):
    st, prof = _stretch(tmp_path, 4, delay_s=0.0, cap_s=0.03)
    st.start()
    _join(st)
    assert st.error is None and st.steps == 0
    assert st.window[0] == st.sync_mark_perf
    assert 0.03 <= st.window[1] - st.window[0] < 0.5
    assert prof.calls[-1][0] == "stop"
    assert "CUT by its cap" in capsys.readouterr().err


def test_the_window_closing_ends_the_stretch(tmp_path, capsys):
    st, prof = _stretch(tmp_path, 4, delay_s=0.0, cap_s=5.0)
    st.start()
    _wait_for(lambda: st.sync_mark_perf is not None)
    fed = [time.perf_counter() + 0.001 * k for k in range(3)]
    for t in fed:
        st.step_completed(t)
    st.window_closed()
    _join(st)
    assert st.error is None
    assert st.window == (fed[0], fed[2]) and st.steps == 2
    assert prof.calls[-1][0] == "stop"
    assert "CUT by the window closing" in capsys.readouterr().err


def test_a_window_that_closes_before_the_delay_is_not_traced(tmp_path):
    st, prof = _stretch(tmp_path, 4, delay_s=5.0)
    st.start()
    st.window_closed()
    _join(st)
    assert "closed before" in st.error and st.window is None
    assert prof.calls == []


def test_a_profiler_that_fails_is_reported_not_raised(tmp_path):
    st, prof = _stretch(tmp_path, 4, delay_s=0.0)
    prof.start_trace = lambda *a, **k: (_ for _ in ()).throw(OSError("full"))
    st.start()
    _join(st)
    assert st.error == "OSError: full" and st.window is None


def _run(tmp_path, monkeypatch, trace):
    monkeypatch.setattr(bench_run, "CACHE_DIR", str(tmp_path))
    cell = {"name": "cfg.cell", "chips": 1}
    return bench_run.Run(cell, {}, {}, seed=0, seconds=1.0, trace=trace)


def test_untraced_run_installs_no_listener_and_starts_no_thread(
        tmp_path, monkeypatch):
    r = _run(tmp_path, monkeypatch, trace=0)
    assert r.step_listener(4) is None and r.stretch is None
    before = set(threading.enumerate())
    r.window_opened(time.perf_counter())
    r.window_closed(time.perf_counter())
    r.join_tracer()
    assert set(threading.enumerate()) == before


def test_traced_run_hands_out_the_stretchs_listener(tmp_path, monkeypatch):
    r = _run(tmp_path, monkeypatch, trace=1)
    listener = r.step_listener(4)
    assert listener == r.stretch.step_completed and r.stretch.period == 4
    assert r.stretch.trace_dir == r.trace_dir
    # never started here: joining a stretch whose thread never ran returns
    r.join_tracer()


class _Ready:
    """Stands in for a step's device scalar."""


def _clock_stamps(monkeypatch, on_step):
    """One StepClock fed 14 steps of a 3-step epoch on a fake clock that
    advances 1 s a reading (each thread reads its own, so the stamps do not
    depend on how the threads interleave): its stamps, and what it told
    ``on_step``."""
    ticks: dict = {}

    def fake_perf_counter():
        me = threading.current_thread().name
        ticks[me] = ticks.get(me, -1) + 1
        return float(ticks[me])

    monkeypatch.setattr(training, "time",
                        types.SimpleNamespace(perf_counter=fake_perf_counter))
    monkeypatch.setattr(training, "hbm_bytes", lambda chips, key: 0)
    told = []
    opened, closed = [], []
    clock = training.StepClock(
        warmup_steps=3, seconds=5.0, on_open=opened.append,
        on_close=lambda t: (closed.append(t), told.append("close")),
        period=3, on_step=(lambda t: told.append(t)) if on_step else None)
    for _ in range(14):
        clock.submit(_Ready(), 8.0)
    clock.finish()
    return clock, opened, closed, told


def test_step_clock_stamps_the_same_with_and_without_the_listener(monkeypatch):
    got = signal.signal(signal.SIGTERM, lambda *a: None)  # the clock's stop
    try:
        plain, opened0, closed0, told0 = _clock_stamps(monkeypatch, False)
        heard, opened1, closed1, told1 = _clock_stamps(monkeypatch, True)
    finally:
        signal.signal(signal.SIGTERM, got)
    assert [s[0] for s in plain.done] == [s[0] for s in heard.done]
    assert (opened0, closed0) == (opened1, closed1)
    assert (plain.t_open, plain.t_close) == (heard.t_open, heard.t_close)
    assert told0 == ["close"]
    # every step inside (t_open, t_close], the closing one before on_close
    inside = [s[0] for s in heard.window_steps()]
    assert told1 == inside + ["close"] and len(inside) % 3 == 0 and inside


def test_busy_device_replays_a_batchers_first_epoch_in_order():
    """``busy_device.py``'s collate: a batcher's first epoch is collated for
    real, every later call hands out the batch of its place in the epoch."""
    from benchmark.tests import busy_device

    class Batcher:
        def __init__(self, n):
            self.n, self.real = n, 0

        def num_batches(self):
            return self.n

        def _collate(self, items, valid):
            self.real += 1
            return ("batch", self.real - 1, tuple(items))

    Batcher._collate = busy_device.replaying(Batcher._collate)
    a, b = Batcher(3), Batcher(1)
    got = [a._collate([k], None) for k in range(8)]
    assert a.real == 3 and got[:3] == [("batch", k, (k,)) for k in range(3)]
    assert [g[1] for g in got] == [0, 1, 2, 0, 1, 2, 0, 1]
    # another batcher (the checks', the Trainer's sample) keeps its own epoch
    assert b._collate(["x"], None) == ("batch", 0, ("x",)) and b.real == 1
