"""Device milliseconds a step (a decoded batch) in the sparse layers' prefix
attention: the self time of the kernel ``sparse_attn_prefill``'s operations
in the traced stretch (the flash kernel that applies each query's block
choice as its mask; the selection's scores and the decode steps' one query a
lane run as compiled operations without a name of their own and are not in
it)."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "sparse_attn_prefill")
