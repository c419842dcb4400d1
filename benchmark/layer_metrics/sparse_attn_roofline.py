"""Share of its roofline the sparse prefix attention reaches: the least time
the chip could take for the keys the rule gives each query (the cost model's
``mechanism_cost``: the selection's scores and the attention over the
attended keys only, by expectation; q, k, v and the compressed keys read, the
output written) over the kernel's device time. A kernel that walks keys the
rule masks out reads low here by exactly that."""

from benchmark.layer_metrics._kernels import kernel_roofline_share


def read(reading):
    return kernel_roofline_share(reading, "sparse_attn_prefill", "sparse_attn")
