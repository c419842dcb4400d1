"""Share of its roofline the ``decode`` program reaches: the least time the
chip could take (``costs.py``; these programs are bound by HBM traffic, the
log line says which) over its device time from the trace."""

from benchmark.layer_metrics._common import roofline_share


def read(reading):
    return roofline_share(reading, "decode")
