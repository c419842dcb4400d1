"""Of the query-key pairs plain causal attention would attend in every layer
(a query: every valid prefix position, and every lane's query of every decode
step), the share the layers did attend, %: the program's counters
(``attn.pairs_window`` + ``attn.pairs_full``) / ``attn.pairs_causal``, from
the few integers the compiled search returns beside the tokens. By arithmetic
about 19 % at 14.3 k positions with 9 window layers of 128 beside 2 full ones
(2/11 and the windows' 1.5 %); 100 % where the window layers attend densely."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    near = window_count(reading, "attn.pairs_window")
    whole = window_count(reading, "attn.pairs_full")
    every = window_count(reading, "attn.pairs_causal")
    if near is None or whole is None or not every:
        return None
    return 100.0 * (near + whole) / every
