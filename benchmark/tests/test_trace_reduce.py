"""The trace reduction: on hand-made events (the arithmetic) and on a small
recorded TPU traces committed beside this file: what ``load_xplane`` extracted from
real ``.xplane.pb`` files of traced stretches on TPU v5e chips, cut down
(``recorded_trace.json``: 50 ms of an XE step on one chip, every operation;
``recorded_trace_dp4.json``: one second of SCST on four chips, module events
and collective operations only)."""

import json
import os

import pytest

from benchmark import trace_reduce as tr

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.mark.parametrize("intervals,seconds", [
    ([], 0.0),
    ([(0, 1e9)], 1.0),
    ([(0, 1e9), (5e8, 2e9)], 2.0),            # overlap counted once
    ([(0, 1e9), (2e9, 3e9)], 2.0),            # a gap is not busy
    ([(2e9, 3e9), (0, 1e9), (1e8, 2e8)], 2.0),  # nested, unsorted
])
def test_union_seconds(intervals, seconds):
    assert tr.union_seconds(intervals) == pytest.approx(seconds)


def test_self_seconds_takes_children_out():
    events = [["while.1", 0, 10e9], ["fusion.2", 1e9, 3e9],
              ["fusion.2", 5e9, 2e9], ["all-reduce.3", 8e9, 1e9],
              ["copy.4", 11e9, 1e9]]
    got = tr.self_seconds(events)
    assert got == pytest.approx({"while.1": 4.0, "fusion.2": 5.0,
                                 "all-reduce.3": 1.0, "copy.4": 1.0})


def test_idle_gaps():
    gaps = tr.idle_gaps([(1e9, 2e9), (1.5e9, 3e9), (5e9, 6e9)], 0, 7e9)
    assert gaps == [(0, 1e9), (3e9, 2e9), (6e9, 1e9)]


def _trace(devices=1):
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [["bench.sync", 100.0, 1e6]]}]}]
    for d in range(devices):
        planes.append({"name": f"/device:TPU:{d}", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_decode(11)", 0, 1e9], ["jit_update(12)", 2e9, 2e9 + d * 1e9],
                ["jit_decode(11)", 6e9, 1e9]]},
            {"name": "XLA Ops", "events": [
                ["while.1", 0, 1e9], ["fusion.7", 0, 5e8],
                ["fusion.9", 2e9, 1e9 + d * 1e9], ["all-reduce.2", 3e9 + d * 1e9, 1e9],
                ["while.1", 6e9, 1e9], ["fusion.7", 6e9, 5e8]]},
        ]})
    return {"planes": planes}


def test_reduce_one_device():
    s = tr.reduce_trace(_trace(), host_spans=[("rl.reward", 1e9, 2e9),
                                              ("rl.decode", 4.4e9, 5.9e9)])
    assert s["window_s"] == pytest.approx(7.0)
    assert s["busy_s"] == pytest.approx(4.0)
    assert s["idle_share_worst"] == pytest.approx(3 / 7)
    d = s["devices"][0]
    assert d["module_s"] == pytest.approx({"jit_decode": 2.0, "jit_update": 2.0})
    assert d["module_n"] == {"jit_decode": 2, "jit_update": 1}
    assert d["collective_s"] == pytest.approx(1.0)
    assert tr.module_run_seconds(s, "update") == pytest.approx(2.0)
    assert tr.module_run_seconds(s, "decode") == pytest.approx(1.0)
    assert tr.module_run_seconds(s, "no_such_module") is None
    ops = dict(s["breakdown"]["device_ops"])
    assert ops == pytest.approx({"while.1": 1.0, "fusion.7": 1.0,
                                 "fusion.9": 1.0, "all-reduce.2": 1.0})
    gaps = dict(s["breakdown"]["idle_gaps"])
    # [1,2] s lies under rl.reward; [4,6] s is 3/4 under rl.decode
    assert gaps == pytest.approx({"host:rl.reward": 1.0, "host:rl.decode": 2.0})
    # the main thread in no span: what the prefetch thread did labels the gap
    s = tr.reduce_trace(_trace(), host_spans=[("rl.reward", 1e9, 2e9)],
                        background_spans=[("prefetch.stage", 3.9e9, 6e9)],
                        window=(0.0, 8e9))
    assert s["window_s"] == pytest.approx(8.0)
    assert dict(s["breakdown"]["idle_gaps"]) == pytest.approx({
        "host:rl.reward": 1.0, "host:waiting_while:prefetch.stage": 2.0,
        "host:outside_every_span": 1.0})


def test_reduce_four_devices_reads_the_idlest():
    s = tr.reduce_trace(_trace(4))
    # device d's update runs d seconds longer (until it meets the next
    # decode at 6 s): busy 4, 5, 6, 6 of the common 7 s; the idlest is read
    busy = [d["busy_s"] for d in s["devices"]]
    assert busy == pytest.approx([4.0, 5.0, 6.0, 6.0])
    assert s["window_s"] == pytest.approx(7.0)
    assert s["idle_share_worst"] == pytest.approx(3 / 7)
    assert s["idle_share_mean"] == pytest.approx(1 - 5.25 / 7)
    assert s["busy_s"] == pytest.approx(5.25)


def test_no_device_plane_is_nothing_to_read():
    assert tr.reduce_trace({"planes": [{"name": "/host:CPU", "lines": []}]}) is None


def test_sync_offset():
    assert tr.sync_offset_ns(_trace(), wall_s_at_mark=2.0) == 2e9 - 100.0
    assert tr.sync_offset_ns({"planes": []}, 2.0) is None


def test_module_name():
    assert tr.module_name("jit_update(1234567)") == "jit_update"
    assert tr.module_name("jit_step") == "jit_step"


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_tpu_trace(recorded):
    """A real trace: the numbers below were read off the events by hand."""
    s = tr.reduce_trace(recorded["trace"], recorded["host_spans"],
                        recorded["window"], recorded["background_spans"])
    want = recorded["expected"]
    assert s["window_s"] == pytest.approx(want["window_s"])
    assert s["busy_s"] == pytest.approx(want["busy_s"])
    assert 100 * s["idle_share_worst"] == pytest.approx(want["idle_share_pct"])
    for name, (secs, runs) in want["modules"].items():
        for d in s["devices"]:
            assert d["module_s"][name] == pytest.approx(secs)
            assert d["module_n"][name] == runs
    assert sum(d["collective_s"] for d in s["devices"]) == pytest.approx(
        want["collective_s"])
    assert 0 < s["busy_s"] <= s["window_s"]


def test_recorded_four_chip_trace_collectives_and_modules():
    with open(os.path.join(HERE, "recorded_trace_dp4.json")) as f:
        rec = json.load(f)
    s = tr.reduce_trace(rec["trace"], window=rec["window"])
    assert [d["device"] for d in s["devices"]] == [0, 1, 2, 3]
    for d in s["devices"]:
        want = rec["expected"][f"/device:TPU:{d['device']}"]
        assert d["collective_s"] == pytest.approx(want["collective_s"])
        assert d["module_s"]["jit_device_update"] == pytest.approx(want["update_s"])
        assert d["module_n"]["jit_device_update"] == want["update_runs"] == 3
        assert set(d["op_self_s"]) == {"all-reduce.1"}
    # one all-reduce of about a millisecond in each of the two whole updates
    assert 1.9e-3 < s["devices"][0]["collective_s"] < 2.0e-3
    # the window's end cuts the third update short (66 of 103 ms): the
    # median execution is a whole one
    assert tr.module_run_seconds(s, "update") == pytest.approx(0.1032, abs=2e-4)
    assert tr.module_run_seconds(s, "decode") == pytest.approx(0.01839, abs=1e-5)
