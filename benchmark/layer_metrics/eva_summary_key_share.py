"""Of the keys the EVA layers' queries attended to in the window (a query:
every valid prefix position, and every lane's query of every decode step),
the share that were summaries, %: the program's counters ``eva.keys_summary``
/ (``eva.keys_exact`` + ``eva.keys_summary``), from the few integers the
compiled search returns beside the tokens. By arithmetic about 27 % over a
prefix of 14336 positions (a mean of 384 summaries beside 1024 exact keys)
and rising through a caption; 0 where no prefix reaches a second window."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    exact = window_count(reading, "eva.keys_exact")
    pooled = window_count(reading, "eva.keys_summary")
    if exact is None or pooled is None or not exact + pooled:
        return None
    return 100.0 * pooled / (exact + pooled)
