"""The busiest held expert's rows over the mean: the program's histogram
``moe.expert_rows`` has one observation a decoded batch for each held expert
of each expert layer, the rows (tokens) that expert computed over the batch's
prefill and steps; this is its largest observation over the mean of the
window's. 1 is an even load; the experts of a step run one after another, so
the busiest one's rows bound the walk. The largest is the process's (a
histogram keeps one maximum), which is the window's too wherever every pass
decodes the same clips with the same weights, as job ``eval`` does."""

from benchmark.layer_metrics._counters import window_pair


def read(reading):
    pair = window_pair(reading)
    if pair is None:
        return None
    first, last = (s.get("histograms", {}).get("moe.expert_rows") for s in pair)
    if not last:
        return None
    count = last["count"] - (first["count"] if first else 0)
    total = last["sum"] - (first["sum"] if first else 0.0)
    if count <= 0 or total <= 0:
        return None
    return last["max"] / (total / count)
