"""Mean milliseconds of a ``ckpt.readback`` span: the ``device_get`` of the
whole train state at an epoch's end, the device-idle core of the epoch
turnover."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_mean(reading, "ckpt.readback")
