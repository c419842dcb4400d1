"""Device milliseconds of one execution of the ``eval_decode`` program (the
``Evaluator``'s compiled beam search, one per step of job ``eval``): the
median duration of its XLA module's events in the trace."""

from benchmark.layer_metrics._common import device_ms_per_run


def read(reading):
    return device_ms_per_run(reading, "eval_decode")
