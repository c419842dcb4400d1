"""Main-thread milliseconds per step inside the program's ``prefetch.wait``
spans: the training loop standing still for its next batch, every get of the
prefetch queue, the one that finds the epoch at its end included. The
in-program twin of ``input_wait_ms_per_step`` (the benchmark's timer around
``next()``)."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "prefetch.wait",
                              _spans.main_threads(reading))
