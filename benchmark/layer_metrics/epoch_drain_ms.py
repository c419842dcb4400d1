"""Mean milliseconds of an epoch's ``rl.epoch.drain`` span: the main thread
reading the last steps' scalars back after ``train_epoch`` returns, which
waits for the queued updates. The device is busy meanwhile: turnover time,
not idle time."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_mean(reading, "rl.epoch.drain")
