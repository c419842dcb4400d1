"""Main-thread milliseconds per step blocked at the head of the
``rl.reward`` span until the decode's rollouts are ready: the host waiting
for the device. Large where the device sets the pace, near 0 where the host
does."""

from benchmark.layer_metrics._common import reward_split


def read(reading):
    parts = reward_split(reading)
    return 1e3 * sum(p[0] for p in parts) / len(parts) if parts else None
