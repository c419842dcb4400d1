"""Of the keys the sparse layers' queries saw in the window (a key/value
group a query: every prefix query of a layer that runs its attention there,
and every lane's query of every decode step), the share they attended to, %:
the program's counters ``sparse.keys_selected`` / ``sparse.keys_visible``,
from the few scalars the compiled decode returns beside the tokens. 100 where
nothing is pruned (every query under the dense length)."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    seen = window_count(reading, "sparse.keys_visible")
    took = window_count(reading, "sparse.keys_selected")
    if not seen or took is None:
        return None
    return 100.0 * took / seen
