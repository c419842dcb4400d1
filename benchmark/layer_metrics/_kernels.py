"""What the readers of a named kernel's device time share (``_common.py`` is
frozen, so it lives here). A Pallas kernel's custom call carries the kernel's
``name`` as its HLO instruction name, so its events on the ``XLA Ops`` line
read ``<name>.<n>``: ``trace_reduce``'s per-operation self seconds, summed
over the operations of that name and averaged over the devices, divided by
the steps completed in the traced stretch. A program without the kernel (this
PR's parent, a configuration whose mixers run as compiled loops) has no such
operation and reads None. The cost side is the configuration's cost model's
``mechanism_cost(model, shape)``, kept with the benchmark."""

from __future__ import annotations

import re

from benchmark import costs
from benchmark.layer_metrics._common import steps_between


def kernel_ms_per_step(reading, name: str):
    """Device milliseconds a step in the operations named ``name.<n>``."""
    tr = reading["trace"]
    if tr is None or reading.get("trace_window") is None:
        return None
    rx = re.compile(r"^" + re.escape(name) + r"(\.\d+)?$")
    per_device = [[v for k, v in d["op_self_s"].items() if rx.match(k)]
                  for d in tr["devices"]]
    steps = steps_between(reading, *reading["trace_window"])
    if not any(per_device) or not steps:
        return None
    return 1e3 * sum(map(sum, per_device)) / len(per_device) / steps


def kernel_roofline_share(reading, name: str, mechanism: str):
    """The least time the chip could take for ``mechanism``'s work over the
    prefix of one step (the configuration's cost model, the published peaks)
    over the kernel's device time a step, in %."""
    from benchmark.training import config_module

    ms = kernel_ms_per_step(reading, name)
    if not ms:
        return None
    try:
        module = config_module(reading["config"], "costs", "mechanism_cost")
    except SystemExit:
        return None
    shape = costs.chip_share(reading["result"]["cost_shape"], reading["chips"])
    cost = module.mechanism_cost(reading["config"]["model"], shape)[mechanism]
    least_s, _bound = costs.roofline(cost, reading["device_kind"])
    return 100.0 * least_s / (ms / 1e3)
