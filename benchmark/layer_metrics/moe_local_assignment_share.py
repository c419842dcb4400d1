"""Of the window's token-expert assignments (a routed token's
``num_experts_per_tok`` choices, over the prefill and every decode step the
beam search ran), the share that fell on experts this chip holds: the
program's counters ``moe.assignments.local`` / ``moe.assignments``, from the
few scalars the compiled decode returns beside the tokens. Under uniform
routing it reads ``experts_held / n_routed_experts`` (3.1 % at 12 of 384)."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    every = window_count(reading, "moe.assignments")
    local = window_count(reading, "moe.assignments.local")
    if not every or local is None:
        return None
    return 100.0 * local / every
