"""Main-thread milliseconds per step inside the program's ``eval.collect``
spans (``jax.device_get`` of a decoded batch's tokens and the read of its
tally): the evaluation loop standing still for the device, summed over the
window and divided by the batches decoded in it. The ``eval`` twin of
``decode_wait_ms_per_step``: near a step where the device sets the pace,
near 0 where the host does."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "eval.collect",
                              _spans.main_threads(reading))
