"""Device milliseconds a step (a decoded batch) in the EVA layers' prefix
attention: the self time of the kernel ``eva_attn_prefill``'s operations in
the traced stretch (the flash kernel that walks, for a tile of queries, the
summaries of the windows before its own and its own window's exact keys up to
the diagonal; the pooling of chunks into summaries and the decode steps' one
query a lane run as compiled operations without a name of their own and are
not in it)."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "eva_attn_prefill")
