"""Share of its roofline the ``update`` program reaches: the least time the
chip could take (the configuration's cost model through ``costs.py``; the
log line says which) over its device time from the trace."""

from benchmark.layer_metrics._common import roofline_share


def read(reading):
    return roofline_share(reading, "update")
