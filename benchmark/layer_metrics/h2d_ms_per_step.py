"""Milliseconds per step the staging thread spends in ``transform`` and the
placement of a batch (the program's ``prefetch.h2d`` spans): the *enqueue* of
``device_put`` / ``put_global``, host time, not the transfer's completion."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "prefetch.h2d")
