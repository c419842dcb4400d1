"""The beam's attention cache at its largest, GiB: the program's gauge
``decode.cache_bytes`` (every leaf of the decode state a batch's encoder pass
returns, a beam of them: for the latent-attention decoder ``batch x beam x
layers x (prefix + max_len) positions x (kv_lora_rank + rope)`` numbers). The
search holds it twice while it reorders the beams."""

from benchmark.layer_metrics._counters import window_pair


def read(reading):
    pair = window_pair(reading)
    if pair is None:
        return None
    value = pair[1].get("gauges", {}).get("decode.cache_bytes")
    return None if value is None else value / 2**30
