"""Device milliseconds a step (a decoded batch) in the linear layers' chunked
scan over the prefix: the self time of the kernel ``linear_attn_prefill``'s
operations in the traced stretch, all linear layers together (a decode step's
one-position recurrence runs as compiled operations without a name of their
own and is not in it)."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "linear_attn_prefill")
