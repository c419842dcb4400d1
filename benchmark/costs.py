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
    ``cost_shape`` as one chip's share (:func:`chip_share`)."""
    from benchmark.training import config_module

    return config_module(config, "costs", "program_cost").program_cost(
        config["model"], shape)


def caption_profile(tokens, chunks: int = 1, pad_id: int = 0) -> dict:
    """The work a batch's captions need, step by step, from the tokens
    themselves: ``tokens`` is ``[batches, lanes a clip, clips, T]`` on the
    host (PAD after a lane's EOS); the result is the mean over the batches of,
    for each step ``t``: ``lanes`` the lanes that hold a token at ``t`` (EOS
    included), ``clips`` the clips that have such a lane, ``steps`` 1 where
    any lane does, and for a program that walks each clip's lanes in
    ``chunks`` slices (the RL update cuts the rollout axis), ``chunk_clips``
    the clips with such a lane summed over the slices and ``chunk_steps`` the
    slices that hold one. A cost model sums over it
    (``cost_models/lstm_captioner.py``); no counter of the program is read."""
    import numpy as np

    held = np.asarray(tokens) != pad_id                     # [N, K, B, T]
    N, K, B, T = held.shape
    if K % chunks:
        raise ValueError(f"{chunks} slices do not divide {K} lanes a clip")
    sliced = held.reshape(N, chunks, K // chunks, B, T).any(2)  # [N, c, B, T]
    mean = lambda x: [float(v) for v in x.mean(0)]  # noqa: E731
    return {"lanes": mean(held.sum((1, 2))),
            "clips": mean(held.any(1).sum(1)),
            "steps": mean(held.any((1, 2))),
            "chunk_clips": mean(sliced.sum((1, 2))),
            "chunk_steps": mean(sliced.any(2).sum(1))}


def chip_share(shape: dict, chips: int) -> dict:
    """A job's ``cost_shape`` (the global batch) as ONE chip's share: ``B``
    and the profile's counts of lanes and clips divided by the chips; the
    profile's ``*steps`` keys stay (every chip walks every step)."""
    out = dict(shape, B=shape["B"] // chips)
    if shape.get("profile"):
        out["profile"] = {
            k: v if k.endswith("steps") else [x / chips for x in v]
            for k, v in shape["profile"].items()}
    return out


def roofline(cost: dict[str, float], device_kind: str) -> tuple[float, str]:
    """(least seconds the chip could take, which peak bounds it)."""
    p = peaks(device_kind)
    by_flops = cost["flops"] / p["bf16_flops_per_s"]
    by_bytes = cost["bytes"] / p["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops >= by_bytes else (by_bytes, "hbm")
