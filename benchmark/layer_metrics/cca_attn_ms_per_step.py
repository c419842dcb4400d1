"""Device milliseconds a step (a decoded batch) in the compressed-latent
layers' prefix attention: the self time of the kernel ``cca_attn_prefill``'s
operations in the traced stretch (the causal grouped-query flash kernel body
of ``full_attn_prefill`` under a name of its own: 4 query heads share each
key tile's copy, keys and values of one width, tiles past the diagonal are
skipped; what comes before it (the two convolutions, the q-k mean, the
lengths, the shift, the rope: ``cca_mix`` in an operation's ``op_name``) and
the decode steps' one query a lane run as compiled operations without a name
of their own and are not in it). A program without the kernel reads None."""

from benchmark.layer_metrics._kernels import kernel_ms_per_step


def read(reading):
    return kernel_ms_per_step(reading, "cca_attn_prefill")
