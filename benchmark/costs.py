"""Operations and bytes of a cell's step programs, and the chip's published
peaks. What a program costs is the architecture's own arithmetic: the
configuration file names its cost model under ``costs`` (a file under
``cost_models/``, or beside a test), and :func:`program_cost` calls that
module's ``program_cost(model, shape) -> {program: {"flops", "bytes"}}`` with
the configuration's ``model``. A configuration that names no cost model, or a
module without that function, is an error and never a default. The table of
peaks (``peaks.json``) and :func:`roofline` are shared by every architecture.

A roofline share is the larger of FLOPs/peak and bytes/peak (the least time
the chip could take) over the program's measured device time; the metric's
log line says which of the two bounds it.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of ``device_kind``; a kind not in the table is an
    error, never a default."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peak for device kind {device_kind!r} "
                       f"in benchmark/peaks.json")
    return table[device_kind]


def program_cost(config: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{program: {"flops", "bytes"}}`` per step on ONE chip's share of the
    batch, by the cost model the configuration names. ``shape`` is the job's
    ``cost_shape`` (``B`` already divided by the chips)."""
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost").program_cost(
        config["model"], shape)


def roofline(cost: dict[str, float], device_kind: str) -> tuple[float, str]:
    """(least seconds the chip could take, which peak bounds it)."""
    p = peaks(device_kind)
    by_flops = cost["flops"] / p["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / p["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")
