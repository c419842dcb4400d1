"""Device milliseconds of one execution of the ``xe`` program (one per
step): the median duration of its XLA module's events in the trace."""

from benchmark.layer_metrics._common import device_ms_per_run


def read(reading):
    return device_ms_per_run(reading, "xe")
