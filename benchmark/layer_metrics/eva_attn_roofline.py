"""Share of its roofline the EVA prefix attention reaches: the least time the
chip could take for the pairs of a query with the exact keys of its window
and the summaries before it (the cost model's ``mechanism_cost``: those pairs
and no other; q, k, v and the summaries read, the output written) over the
kernel's device time. A kernel that walks tiles no query of the tile sees, or
attends densely where a summary stands, reads low here by exactly that."""

from benchmark.layer_metrics._kernels import kernel_roofline_share


def read(reading):
    return kernel_roofline_share(reading, "eva_attn_prefill", "eva_attn")
