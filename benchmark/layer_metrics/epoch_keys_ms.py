"""Mean milliseconds of an epoch's ``rl.epoch.keys`` span: the epoch's
sampling key (``device_key`` / ``device_fold_in``, a program per epoch
number). Small where the benchmark warmed the keys in set-up."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_mean(reading, "rl.epoch.keys")
