"""Milliseconds per step inside ``Batcher._collate`` (the program's
``data.collate`` spans, one a batch, on whichever thread collates: the
prefetch worker in training). It overlaps the main thread's work except where
the loop waits for it (``prefetch_wait_ms_per_step``)."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "data.collate")
