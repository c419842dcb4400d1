"""Share of its roofline the ``eval_decode`` program reaches: the least time
the chip could take for the emitted captions (the configuration's cost model
through ``costs.py``; the log line says which peak bounds it) over its device
time from the trace."""

from benchmark.layer_metrics._common import roofline_share


def read(reading):
    return roofline_share(reading, "eval_decode")
