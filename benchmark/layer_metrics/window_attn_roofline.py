"""Share of its roofline the window layers' prefix attention reaches: the
least time the chip could take for the pairs of a query with the keys inside
its band (the cost model's ``mechanism_cost``: those pairs and no other; q, k,
v read once, the output written) over the kernel's device time. A kernel that
walks tiles its band only grazes, or attends densely where a window stands,
reads low here by exactly that. The cost model counts every slot of a clip,
the kernel skips the query tiles past a clip's valid slots: at the corpus'
mean of slots this reads up to 14 % high (the configuration's ``assumed``)."""

from benchmark.layer_metrics._kernels import kernel_roofline_share


def read(reading):
    return kernel_roofline_share(reading, "window_attn_prefill", "window_attn")
