"""End-to-end utilization: the FLOPs the step's captions need (the
configuration's cost model through ``costs.py`` over the job's profile of
the tokens that ran; matrix multiplications only, recomputation and steps
past a caption's end not counted) times steps per
second on the benchmark's clock, over the chip's peak. Not a roofline share:
idle time is in it."""

from benchmark import costs


def read(reading):
    steps = reading["result"].get("steps", ())
    if len(steps) < 2:
        return None
    shape = costs.chip_share(reading["result"]["cost_shape"], reading["chips"])
    flops = sum(c["flops"] for c in costs.program_cost(
        reading["config"], shape).values())
    t0, t1 = reading["window"]
    peak = costs.peaks(reading["device_kind"])["bf16_flops_per_s"]
    return 100.0 * flops * len(steps) / (t1 - t0) / peak
