"""Cost model of the attention-LSTM captioner (``configs/msrvtt_attention.json``
names it under ``costs``): operations and bytes of its step programs, from
shapes and from the captions that ran. The benchmark's copy of the arithmetic
in ``cst_captioning_tpu/obs/flops.py``, fed from the configuration's ``model``
instead of module constants; moved here from ``costs.py`` (PR 27).

Conventions: FLOPs count matrix multiplications only, ``2*m*n*k``; backward
is twice the forward (3x overall). Bytes are an explicit traffic model of the
scan step's working set: every decoder weight and the attention bank are read
once per step, the per-step ``[rows, V]`` f32 logits are written and read once
(they do not fit the chip's fast memory at these widths), the backward moves
twice the forward's bytes, features are read once in f32.

What is counted is the work the captions need, not the steps a program
happens to scan (PR 34): a step ``t`` costs the token FLOPs and the logits of
the lanes that still hold a token at ``t`` (EOS included), the bank of the
clips that have such a lane, and the weights once if any lane does; a step
past the batch's longest caption costs nothing, whether the program runs it
or not. The job says which lanes those are in ``shape["profile"]``
(:func:`full_profile` documents the keys), taken by the benchmark's own code
from the tokens themselves; without one every caption is taken to be
``max_len`` long, which is the count as it was before, to the last digit. So
a program that stops at the longest caption and one that scans all
``max_len`` positions are held to the same least time, and neither can read
over 100 % by skipping padding.
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


def _step_bytes(model: dict, B, rows, reads=1, param_bytes=4, act_bytes=2):
    """One scan step over ``rows`` lanes of ``B`` clips, the decoder's
    weights read ``reads`` times."""
    E, H, A, V = (model["d_embed"], model["d_hidden"], model["d_att"],
                  model["vocab_size"])
    weights = param_bytes * (H * A + (2 * E) * (4 * H) + H * (4 * H) + H * V)
    bank = B * memory_slots(model) * (E + A) * act_bytes
    return reads * weights + bank + 2 * rows * V * 4


def _enc_bytes(model: dict, B: int, param_bytes=4, act_bytes=2):
    E, A = model["d_embed"], model["d_att"]
    feat = sum(d for _, d in model["modalities"])
    return (B * model["max_frames"] * feat * 4
            + B * memory_slots(model) * (E + A) * act_bytes
            + param_bytes * (feat * E + E * A))


def full_profile(T: int, B: int, lanes: int, chunks: int = 1) -> dict:
    """The profile of a batch whose every caption is ``T`` long. Keys, each a
    list over the steps ``t < T``, means over the batches the job looked at:
    ``lanes`` the lanes that hold a token at ``t`` (EOS included), ``clips``
    the clips that have such a lane, ``steps`` 1 where any lane does (the
    share of batches, as a mean); and for a program that walks the lanes in
    ``chunks`` slices of each clip's lanes (the RL update), ``chunk_clips`` the
    clips with such a lane summed over the slices and ``chunk_steps`` the
    slices that hold one. Counts are of ONE chip's share; the ``*steps`` keys
    do not shrink with the chips (``costs.chip_share``)."""
    return {"lanes": [float(lanes)] * T, "clips": [float(B)] * T,
            "steps": [1.0] * T, "chunk_clips": [float(chunks * B)] * T,
            "chunk_steps": [float(chunks)] * T}


def _scan(model: dict, lanes, clips, steps) -> tuple[float, float]:
    """(token FLOPs, step bytes) of one forward walk over a profile: the
    steps' ``_step_bytes(model, clips_t, lanes_t)`` with the weights read
    ``steps_t`` times."""
    _, tok = enc_and_per_tok_flops(model)
    return (sum(lanes) * tok,
            sum(_step_bytes(model, c, n, reads=s)
                for n, c, s in zip(lanes, clips, steps)))


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{program: {"flops", "bytes"}}`` per step on ONE chip's share of the
    batch. ``shape``: ``{"kind": "cst", "B", "K", "chunks"}``, ``{"kind":
    "xe", "B"}`` or ``{"kind": "eval", "B", "beam"}``, each with an optional
    ``"profile"`` (:func:`full_profile`); ``B`` is the clips this chip holds."""
    T = model["max_len"]
    enc, _ = enc_and_per_tok_flops(model)
    B, kind = shape["B"], shape["kind"]
    lanes = {"xe": 1, "eval": shape.get("beam", 1), "cst": shape.get("K", 1)}[kind] * B
    chunks = shape.get("chunks", 1) if kind == "cst" else 1
    p = shape.get("profile") or full_profile(T, B, lanes, chunks)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    whole, whole_bytes = _scan(model, p["lanes"], p["clips"], p["steps"])
    if kind == "xe":
        return {"xe": {"flops": 3.0 * (B * enc + whole),
                       "bytes": 3.0 * (_enc_bytes(model, B) + whole_bytes)}}
    if kind == "eval":
        # beam search: one encoder pass a clip, then the beam's lanes a step
        return {"eval_decode": {"flops": B * enc + whole,
                                "bytes": _enc_bytes(model, B) + whole_bytes}}
    # scb baseline: K sampled lanes, no greedy lane. The update walks the
    # lanes in ``chunks`` slices: a slice reads the weights and its clips'
    # bank at every step it holds a token
    _, sliced_bytes = _scan(model, p["lanes"], p["chunk_clips"],
                            p["chunk_steps"])
    return {
        "decode": {"flops": B * enc + whole,
                   "bytes": _enc_bytes(model, B) + whole_bytes},
        "update": {"flops": 3.0 * (B * enc + whole),
                   "bytes": 3.0 * (_enc_bytes(model, B) + sliced_bytes)},
    }
