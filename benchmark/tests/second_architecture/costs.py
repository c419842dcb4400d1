"""Cost model of the rehearsal's second architecture (``config.json`` names
it under ``costs``): its own arithmetic, one memory slot a modality. FLOPs are
matrix multiplications, ``2*m*n*k``, the backward pass twice the forward;
bytes are the weights and the per-step logits, read and written once."""

from __future__ import annotations


def _flops(model):
    E, H, A, V = (model[k] for k in ("d_embed", "d_hidden", "d_att", "vocab_size"))
    M = len(model["modalities"])
    encoder = sum(2 * width * E for _, width in model["modalities"]) + 2 * M * E * A
    token = (2 * H * A + 2 * M * A + 2 * M * E + 2 * (2 * E) * 4 * H
             + 2 * H * 4 * H + 2 * H * V)
    return float(encoder), float(token)


def _bytes(model, rows):
    E, H, A, V = (model[k] for k in ("d_embed", "d_hidden", "d_att", "vocab_size"))
    weights = 4 * (H * A + 2 * E * 4 * H + H * 4 * H + H * V)
    return float(weights + 2 * rows * V * 4)


def program_cost(model, shape):
    """``{program: {"flops", "bytes"}}`` a step on one chip's ``B`` clips."""
    T, B = model["max_len"], shape["B"]
    encoder, token = _flops(model)
    if shape["kind"] == "xe":
        return {"xe": {"flops": 3 * B * (encoder + T * token),
                       "bytes": 3 * T * _bytes(model, B)}}
    if shape["kind"] == "eval":
        rows = shape["beam"] * B
        return {"eval_decode": {"flops": B * encoder + rows * T * token,
                                "bytes": T * _bytes(model, rows)}}
    rows = shape["K"] * B
    forward = B * encoder + rows * T * token
    return {"decode": {"flops": forward, "bytes": T * _bytes(model, rows)},
            "update": {"flops": 3 * forward, "bytes": 3 * T * _bytes(model, rows)}}
