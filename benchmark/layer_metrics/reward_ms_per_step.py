"""Host milliseconds per step scoring the rollouts: the ``rl.reward`` span
from the moment its rollouts were ready on the device (the benchmark's stamp
on the decode's output) to its end — read-back, consensus scoring,
advantage. The wait for the decode, which the span also covers, is
``decode_wait_ms_per_step``."""

from benchmark.layer_metrics._common import reward_split


def read(reading):
    parts = reward_split(reading)
    return 1e3 * sum(p[1] for p in parts) / len(parts) if parts else None
