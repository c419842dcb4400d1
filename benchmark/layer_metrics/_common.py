"""Helpers the readers share. A reader gets the run's ``reading`` (the job's
result, the reduced trace or None, the program's spans on the benchmark's
clock, the window) and returns a number, or None when there is nothing to
read — the harness then leaves the metric out of the line."""

from __future__ import annotations

from benchmark import costs, trace_reduce


def window_spans(reading, names) -> list[dict]:
    t0, t1 = reading["window"]
    return [s for s in reading["spans"]
            if s["name"] in names and s["t0"] >= t0 and s["t1"] <= t1]


def steps_between(reading, t0: float, t1: float) -> int:
    return sum(t0 < s[0] <= t1 for s in reading["result"]["steps"])


def device_ms_per_run(reading, program: str):
    """Device milliseconds of one execution of the job's ``program`` (the
    median over the traced stretch's executions), from the XLA module events."""
    tr = reading["trace"]
    pattern = reading["result"].get("modules", {}).get(program)
    if tr is None or not pattern:
        return None
    secs = trace_reduce.module_run_seconds(tr, pattern)
    return None if secs is None else 1e3 * secs


def roofline_share(reading, program: str):
    """The least time the chip could take for ``program`` (benchmark's cost
    model over the published peaks) over its measured device time, in %."""
    ms = device_ms_per_run(reading, program)
    if ms is None:
        return None
    shape = costs.chip_share(reading["result"]["cost_shape"], reading["chips"])
    cost = costs.program_cost(reading["config"], shape)[program]
    least_s, _bound = costs.roofline(cost, reading["device_kind"])
    return 100.0 * least_s / (ms / 1e3)


def reward_split(reading) -> list[tuple[float, float]]:
    """(seconds waiting for the decode, seconds scoring) of every
    ``rl.reward`` span in the window. The k-th span of the run reads the
    k-th decode's rollouts; the job stamped when each became ready."""
    ready = reading["result"].get("marks", {}).get("decode_ready", ())
    spans = sorted((s for s in reading["spans"] if s["name"] == "rl.reward"),
                   key=lambda s: s["t0"])
    if not spans or len(ready) < len(spans):
        return []
    t0, t1 = reading["window"]
    parts = []
    for s, r in zip(spans, ready):
        if s["t0"] >= t0 and s["t1"] <= t1:
            r = min(max(r, s["t0"]), s["t1"])
            parts.append((r - s["t0"], s["t1"] - r))
    return parts
