"""From a ``jax.profiler`` trace to numbers: the yardstick's reduction.

``load_xplane`` turns an ``.xplane.pb`` into plain data (planes -> lines ->
``[name, start_ns, dur_ns]`` events; nothing but JAX is needed to read it),
``reduce_trace`` turns that into per-device busy time, idle share, seconds per
XLA module and per operation (self time: an operation's duration minus the
operations nested inside it, so a ``while`` does not count its body twice),
collective seconds, and the longest idle gaps labelled by what the host was
doing. ``benchmark/tests/`` checks it on a small recorded TPU trace.

What is on a TPU device plane (``/device:TPU:<n>``): a line ``XLA Modules``
with one event per executed program, named ``<module>(<program id>)``, and a
line ``XLA Ops`` with one event per executed HLO operation. Times are
nanoseconds from the start of the trace.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
MODULES_LINE, OPS_LINE = "XLA Modules", "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute"
    r"|collective-broadcast)")
SYNC_MARK = "bench.sync"
# an idle stretch shorter than this is the device's own dispatch gap, not
# something the host can be asked about
MIN_GAP_S = 1e-3


def load_xplane(path: str, keep_lines=(MODULES_LINE, OPS_LINE)) -> dict:
    """Planes, lines and events of one ``.xplane.pb`` as plain lists. Device
    planes keep only ``keep_lines``; host planes keep the events whose name
    starts with ``bench.`` (the benchmark's own annotations)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = bool(DEVICE_PLANE.match(plane.name))
        if not on_device and not plane.name.startswith("/host:"):
            continue
        lines = []
        for line in plane.lines:
            if on_device and line.name not in keep_lines:
                continue
            events = [[op_name(e.name), float(e.start_ns), float(e.duration_ns)]
                      for e in line.events
                      if on_device or e.name.startswith("bench.")]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def op_name(event_name: str) -> str:
    """An ``XLA Ops`` event is named by its whole HLO instruction,
    ``%fusion.12 = f32[...] fusion(...)``: keep ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def module_name(event_name: str) -> str:
    """``jit_update(1234)`` -> ``jit_update``."""
    return re.sub(r"\(\d+\)$", "", event_name)


def union_seconds(intervals) -> float:
    """Total length of the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def self_seconds(events) -> dict[str, float]:
    """Seconds per event name on one line, children's time taken out.
    Events on a line nest (a ``while`` holds its body) and never cross."""
    out: dict[str, float] = {}
    stack: list[list] = []   # [name, end_ns, self_ns]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, _, self_ns = stack.pop()
            out[name] = out.get(name, 0.0) + max(self_ns, 0.0) / 1e9

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        close(start)
        if stack:
            stack[-1][2] -= dur
        stack.append([name, start + dur, dur])
    close(float("inf"))
    return out


def idle_gaps(intervals, t0: float, t1: float):
    """``(start_ns, dur_ns)`` of the stretches of ``[t0, t1]`` no interval
    covers."""
    gaps, cur = [], t0
    for s, e in sorted(intervals):
        if s > cur:
            gaps.append((cur, min(s, t1) - cur))
        cur = max(cur, e)
        if cur >= t1:
            break
    if cur < t1:
        gaps.append((cur, t1 - cur))
    return [g for g in gaps if g[1] > 0]


def _clip(events, t0, t1):
    return [[n, max(s, t0), min(s + d, t1) - max(s, t0)]
            for n, s, d in events if s + d > t0 and s < t1]


def reduce_trace(trace: dict, host_spans=(), window=None,
                 background_spans=()) -> dict | None:
    """The reduction. ``host_spans``: ``(name, start_ns, end_ns)`` of what the
    host's main thread was doing, on the trace's clock, for labelling idle
    gaps; a gap that none of them covers is labelled by ``background_spans``
    (what another thread was doing while the main thread waited).
    ``window``: ``(t0_ns, t1_ns)`` to reduce over; by default from the first
    to the last device event. Returns None when no device plane holds an
    operation (a CPU trace, an empty trace)."""
    devices = {}
    for plane in trace["planes"]:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
            devices[int(m.group(1))] = lines
    if not devices:
        return None
    if window is None:
        every = [e for lines in devices.values() for evs in lines.values()
                 for e in evs]
        window = (min(e[1] for e in every), max(e[1] + e[2] for e in every))
    t0, t1 = window
    window_s = (t1 - t0) / 1e9

    per_device = []
    for dev in sorted(devices):
        lines = devices[dev]
        ops = _clip(lines.get(OPS_LINE, []), t0, t1)
        mods = _clip(lines.get(MODULES_LINE, []), t0, t1)
        busy_src = ops or mods
        spans = [(s, s + d) for _, s, d in busy_src]
        module_s: dict[str, float] = {}
        module_n: dict[str, int] = {}
        runs: dict[str, list[float]] = {}
        for name, s, d in mods:
            key = module_name(name)
            module_s[key] = module_s.get(key, 0.0) + d / 1e9
            module_n[key] = module_n.get(key, 0) + 1
            runs.setdefault(key, []).append(d / 1e9)
        op_s = self_seconds(ops)
        per_device.append({
            "device": dev,
            "busy_s": union_seconds(spans),
            "module_s": module_s, "module_n": module_n,
            "module_runs_s": runs,
            "op_self_s": op_s,
            "collective_s": sum(v for k, v in op_s.items()
                                if COLLECTIVE.match(k)),
            "gaps": idle_gaps(spans, t0, t1),
        })

    busy = [d["busy_s"] for d in per_device]
    first = per_device[0]
    gaps = sorted((g for g in first["gaps"] if g[1] / 1e9 >= MIN_GAP_S),
                  key=lambda g: -g[1])
    labelled: dict[str, float] = {}
    for start, dur in gaps:
        label = _host_label(host_spans, background_spans, start, start + dur)
        labelled[label] = labelled.get(label, 0.0) + dur / 1e9
    ops_total: dict[str, float] = {}
    for d in per_device:
        for k, v in d["op_self_s"].items():
            ops_total[k] = ops_total.get(k, 0.0) + v / len(per_device)
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy),
        # the chip the others wait for is the busiest; the share a user
        # loses is read on the idlest
        "idle_share_worst": 1.0 - min(busy) / window_s,
        "idle_share_mean": 1.0 - sum(busy) / len(busy) / window_s,
        "devices": per_device,
        "breakdown": {"device_ops": top(ops_total),
                      "idle_gaps": top(labelled)},
    }


def _host_label(host_spans, background_spans, g0: float, g1: float) -> str:
    """The host span that covers most (at least half) of the gap."""
    for spans, prefix in ((host_spans, "host:"),
                          (background_spans, "host:waiting_while:")):
        best, best_cover = None, 0.0
        for name, s, e in spans:
            cover = min(e, g1) - max(s, g0)
            if cover > best_cover:
                best, best_cover = name, cover
        if best is not None and best_cover >= 0.5 * (g1 - g0):
            return prefix + best
    return "host:outside_every_span"


def sync_offset_ns(trace: dict, wall_s_at_mark: float) -> float | None:
    """Trace-clock nanoseconds = wall seconds * 1e9 - offset, from the one
    ``bench.sync`` annotation whose wall-clock start the benchmark noted."""
    for plane in trace["planes"]:
        if not plane["name"].startswith("/host:"):
            continue
        for line in plane["lines"]:
            for name, start, _ in line["events"]:
                if name == SYNC_MARK:
                    return wall_s_at_mark * 1e9 - start
    return None


def module_run_seconds(summary: dict, pattern: str) -> float | None:
    """Median device seconds of ONE execution of the modules matching
    ``pattern`` (the median leaves out the executions the stretch's two ends
    cut short), averaged over the devices."""
    rx = re.compile(pattern)
    per_device = []
    for d in summary["devices"]:
        durs = sorted(x for k, v in d["module_runs_s"].items()
                      if rx.search(k) for x in v)
        if durs:
            per_device.append(durs[len(durs) // 2])
    return sum(per_device) / len(per_device) if per_device else None
