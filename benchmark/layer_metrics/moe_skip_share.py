"""Of the window's token-expert assignments (a token's one choice a layer,
over the prefill and every decode step the beam search ran), the share whose
router chose the no-expert output, %: the program's counters
``moe.assignments.skipped`` / ``moe.assignments``, from the few integers the
compiled search returns beside the tokens. Such a row runs no expert in that
layer (mixture of depths); under a uniform choice over the router's 17
outputs it reads 1/17 = 5.9 %, which is what the cost model counts: above it
the cost model's expert FLOPs are an over-reading, under it an under-reading.
A program without the counter (a router that always chooses an expert) reads
None."""

from benchmark.layer_metrics._counters import window_count


def read(reading):
    every = window_count(reading, "moe.assignments")
    skipped = window_count(reading, "moe.assignments.skipped")
    if not every or skipped is None:
        return None
    return 100.0 * skipped / every
