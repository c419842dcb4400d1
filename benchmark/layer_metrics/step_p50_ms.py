"""Median time between the completions of consecutive steps in the window,
on the benchmark's clock (each completion is a ``block_until_ready``)."""

import numpy as np


def read(reading):
    t = [s[0] for s in reading["result"].get("steps", ())]
    return 1e3 * float(np.median(np.diff(t))) if len(t) > 1 else None
