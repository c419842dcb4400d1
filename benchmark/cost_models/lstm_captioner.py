"""Cost model of the attention-LSTM captioner (``configs/msrvtt_attention.json``
names it under ``costs``): operations and bytes of its step programs, from
shapes. The benchmark's copy of the arithmetic in
``cst_captioning_tpu/obs/flops.py`` and ``bench.py::_program_roofline``
(rounds 4-5), fed from the configuration's ``model`` instead of module
constants; moved here from ``costs.py`` (PR 27), unchanged to the last digit.

Conventions: FLOPs count matrix multiplications only, ``2*m*n*k``; backward
is twice the forward (3x overall). Bytes are an explicit traffic model of the
scan step's working set: every decoder weight and the attention bank are read
once per step, the per-step ``[rows, V]`` f32 logits are written and read once
(they do not fit the chip's fast memory at these widths), the backward moves
twice the forward's bytes, features are read once in f32.
"""

from __future__ import annotations


def memory_slots(model: dict) -> int:
    """Attention slots per clip: one per modality when the encoder
    mean-pools, one per frame and modality otherwise."""
    n_mod = len(model["modalities"])
    return n_mod if model["encoder"] == "meanpool" else n_mod * model["max_frames"]


def enc_and_per_tok_flops(model: dict) -> tuple[float, float]:
    """(encoder pass, one decoded or teacher-forced token) FLOPs per row."""
    E, H, A, V = (model["d_embed"], model["d_hidden"], model["d_att"],
                  model["vocab_size"])
    M, F = memory_slots(model), model["max_frames"]
    feat = sum(d for _, d in model["modalities"])
    frames = 1 if model["encoder"] == "meanpool" else F
    enc = 2 * frames * feat * E + 2 * M * E * A
    lstm = 2 * (E + E) * (4 * H) + 2 * H * (4 * H)
    per_tok = 2 * H * A + 2 * M * A + 2 * M * E + lstm + 2 * H * V
    return float(enc), float(per_tok)


def _step_bytes(model: dict, B: int, rows: int, param_bytes=4, act_bytes=2):
    E, H, A, V = (model["d_embed"], model["d_hidden"], model["d_att"],
                  model["vocab_size"])
    weights = param_bytes * (H * A + (2 * E) * (4 * H) + H * (4 * H) + H * V)
    bank = B * memory_slots(model) * (E + A) * act_bytes
    return weights + bank + 2 * rows * V * 4


def _enc_bytes(model: dict, B: int, param_bytes=4, act_bytes=2):
    E, A = model["d_embed"], model["d_att"]
    feat = sum(d for _, d in model["modalities"])
    return (B * model["max_frames"] * feat * 4
            + B * memory_slots(model) * (E + A) * act_bytes
            + param_bytes * (feat * E + E * A))


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{program: {"flops", "bytes"}}`` per step on ONE chip's share of the
    batch. ``shape``: ``{"kind": "cst", "B", "K", "chunks"}`` or
    ``{"kind": "xe", "B"}``; ``B`` is the rows this chip holds."""
    T = model["max_len"]
    enc, tok = enc_and_per_tok_flops(model)
    B = shape["B"]
    if shape["kind"] == "xe":
        return {"xe": {
            "flops": 3.0 * B * (enc + T * tok),
            "bytes": 3.0 * (_enc_bytes(model, B) + T * _step_bytes(model, B, B)),
        }}
    K, chunks = shape["K"], shape["chunks"]
    return {
        # scb baseline: K sampled lanes, no greedy lane; all T steps (an
        # upper bound on work: the loop exits when every lane has ended)
        "decode": {
            "flops": B * (enc + K * T * tok),
            "bytes": _enc_bytes(model, B) + T * _step_bytes(model, B, K * B),
        },
        "update": {
            "flops": 3.0 * B * (enc + K * T * tok),
            "bytes": 3.0 * (_enc_bytes(model, B) + chunks * T
                            * _step_bytes(model, B, K * B // chunks)),
        },
    }
