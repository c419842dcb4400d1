"""Share of its roofline the linear layers' prefix scan reaches: the least
time the chip could take for the recurrence (the cost model's
``mechanism_cost``: ``4 d^2`` FLOPs a head and position, q, k, v read and the
output and final state written; at these sizes the memory bounds it) over the
kernel's device time. A chunked scan does more FLOPs than the recurrence needs
and is held to the recurrence's."""

from benchmark.layer_metrics._kernels import kernel_roofline_share


def read(reading):
    return kernel_roofline_share(reading, "linear_attn_prefill", "linear_attn")
