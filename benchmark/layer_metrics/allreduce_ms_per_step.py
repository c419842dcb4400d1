"""Device milliseconds per step in collective operations (self time of the
``all-reduce`` family on the ``XLA Ops`` line), averaged over the chips, over the steps
that completed in the traced stretch.
Total, not exposed: what overlaps compute is in it too."""


def read(reading):
    tr = reading["trace"]
    if tr is None or reading["chips"] < 2:
        return None
    from benchmark.layer_metrics._common import steps_between

    steps = steps_between(reading, *reading["trace_window"])
    secs = sum(d["collective_s"] for d in tr["devices"]) / len(tr["devices"])
    return 1e3 * secs / steps if steps else None
