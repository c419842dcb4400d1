"""Host milliseconds per step in the corpus scorers of a pass (the program's
``eval.score`` spans: the metric table over the whole split, at a pass's
drain, after its last decode), summed over the window and divided by the
batches decoded in it. The per-caption half of scoring runs on the worker
pool beside the decode and is not in it."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "eval.score")
