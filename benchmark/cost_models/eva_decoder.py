"""Cost model of the EVA-attention byte-level caption decoder
(``configs/evabyte_8l.json`` names it under ``costs``): operations and bytes of
its beam-search evaluation, from the configuration's ``model`` sizes and the
captions that ran. Written from the layer equations (``reference_eva.py``),
not from the program: what is counted is the work the model needs, the least
a right program does, whatever implements it.

Conventions (``cost_models/lstm_captioner.py`` has the same): FLOPs count
matrix multiplications only, ``2*m*n*k`` (the pooling of a chunk into its
summary is elementwise work of ``6 h`` a position and is not counted); a step
``t`` costs the token FLOPs, the state traffic and the logits of the lanes
that still hold a token at ``t``, and the weights once if any lane does; a
step past the batch's longest caption costs nothing, whether the program runs
it or not.

The program runs a batch as two programs and so does this model:
``eval_prefill`` (the video prefix through the stack, once a clip) and
``eval_decode`` (the beam search from it). ``mfu_end_to_end`` sums both.

- **The sets.** A query at position ``i`` attends to ``i % window + 1`` exact
  keys and ``(window / chunk) (i // window)`` summaries
  (:func:`attended`): 4 ``hidden_size`` FLOPs a pair (scores and values, all
  heads).
- **Prefix**, once a clip, over all ``max_frames`` slots a modality (the
  profile does not say which slots are missing: a corpus whose clips hold
  fewer counts up to that share too much here, which the configuration's file
  states): the projector; every layer's four projections and FFN and its
  pairs, except that the last layer leaves only its keys, values and summaries
  and runs no query, no output projection and no FFN.
- **A step**, for every lane that holds a token: the same a position at
  ``max_frames + t``, and the head's first ``vocab_size`` columns (the block
  plain decoding reads).
- **Bytes.** Prefix: the weights it uses once a batch, the features read, the
  summaries written; each layer's q, k, v read and output written and its
  summaries read. A step: every weight once if any lane holds a token; **the
  clip's summaries and its exact keys of the prefix's last window once a clip
  that holds a token** (a clip of ``max_frames`` valid slots, the convention
  above, ends on a window's edge and has none of the second: a clip that
  holds fewer reads up to ``window - 1`` exact keys more, so the decode's
  roofline reads low by that, never high); a lane's own caption keys and own
  summaries read, its new key and value written, the ``[lanes, V]`` float32
  logits written and read once. The beam's reordering copy of its state is
  the program's own.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def n_prefix(model: dict) -> int:
    return len(model["modalities"]) * model["max_frames"]


def layer_weights(model: dict, kv_only: bool = False) -> int:
    """One layer's matrices: four projections and the gated FFN; ``kv_only``
    the two the prefix's last layer runs."""
    h = model["hidden_size"]
    if kv_only:
        return 2 * h * h
    return 4 * h * h + 3 * h * model["intermediate_size"]


def parameter_count(model: dict) -> int:
    """Matrices only (the norms' vectors and the heads' ``phi`` and ``mu``
    are 0.2 M of 1.635 B)."""
    h = model["hidden_size"]
    feat = sum(d for _, d in model["modalities"])
    return (model["num_hidden_layers"] * layer_weights(model)
            + h * model["vocab_size"] * (1 + model["num_pred_heads"]) + feat * h)


def attended(model: dict, position: int) -> tuple[int, int]:
    """(exact keys, summaries) the query at ``position`` attends to."""
    window = model["window_size"]
    return (position % window + 1,
            (window // model["chunk_size"]) * (position // window))


def prefix_pairs(model: dict, positions: int) -> tuple[int, int]:
    """(query-key pairs over exact keys, over summaries) of the queries at
    positions ``0 .. positions``: :func:`attended` summed, in closed form."""
    window = model["window_size"]
    whole, rest = divmod(positions, window)
    exact = whole * window * (window + 1) // 2 + rest * (rest + 1) // 2
    pooled = (window // model["chunk_size"]) * (
        window * whole * (whole - 1) // 2 + rest * whole)
    return exact, pooled


def pair_flops(model: dict) -> float:
    """Scores and values of one query-key pair, all heads."""
    return 4.0 * model["hidden_size"]


def prefill_clip_flops(model: dict) -> float:
    P, h = n_prefix(model), model["hidden_size"]
    feat = sum(d for _, d in model["modalities"])
    runs = model["num_hidden_layers"] - 1
    return (2.0 * model["max_frames"] * feat * h
            + runs * (P * 2.0 * layer_weights(model)
                      + pair_flops(model) * sum(prefix_pairs(model, P)))
            + P * 2.0 * layer_weights(model, kv_only=True))


def step_token_flops(model: dict, t: int) -> float:
    """One decoded token at caption position ``t``, head included."""
    return (2.0 * model["hidden_size"] * model["vocab_size"]
            + model["num_hidden_layers"] * (
                2.0 * layer_weights(model)
                + pair_flops(model) * sum(attended(model, n_prefix(model) + t))))


def weight_bytes(model: dict, prefill: bool = False) -> float:
    """Bytes of the weights one pass reads: a step's the stack and the
    head's first block; the prefix's the projector and what its layers run."""
    b = _BYTES[model["param_dtype"]]
    h, L = model["hidden_size"], model["num_hidden_layers"]
    if not prefill:
        return float(b * (L * layer_weights(model) + h * model["vocab_size"]))
    return float(b * (sum(d for _, d in model["modalities"]) * h
                      + (L - 1) * layer_weights(model)
                      + layer_weights(model, kv_only=True)))


def mechanism_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"eva_attn"}``: operations and bytes of the attention itself over the
    prefix of one batch (the layers whose queries run there): what the
    kernel's roofline share is taken against. The pairs of the two sets and no
    other; a layer reads q, k, v and its summaries and writes its output
    once."""
    B, P, h = shape["B"], n_prefix(model), model["hidden_size"]
    b, runs = _BYTES[model["dtype"]], model["num_hidden_layers"] - 1
    return {"eva_attn": {
        "flops": float(B * runs * pair_flops(model) * sum(prefix_pairs(model, P))),
        "bytes": float(B * runs * (4 * P + 2 * (P // model["chunk_size"])) * h * b)}}


def full_profile(T: int, B: int, lanes: int) -> dict:
    return {"lanes": [float(lanes)] * T, "clips": [float(B)] * T,
            "steps": [1.0] * T}


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"eval_prefill", "eval_decode"}``, each ``{"flops", "bytes"}`` per
    decoded batch on ONE chip's share. ``shape``: ``{"kind": "eval", "B",
    "beam"}`` with an optional ``"profile"``."""
    if shape["kind"] != "eval":
        raise ValueError(
            f"the EVA decoder is costed for job eval alone, not "
            f"{shape['kind']!r}: its configuration has no training cell")
    T, B = model["max_len"], shape["B"]
    p = shape.get("profile") or full_profile(T, B, shape.get("beam", 1) * B)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    P, h, L = n_prefix(model), model["hidden_size"], model["num_hidden_layers"]
    b, window = _BYTES[model["dtype"]], model["window_size"]
    chunks = P // model["chunk_size"]       # the prefix's whole chunks
    feat = sum(d for _, d in model["modalities"])
    prefill = {
        "flops": B * prefill_clip_flops(model),
        "bytes": weight_bytes(model, prefill=True)
        + B * model["max_frames"] * feat * 4 + B * L * 2 * chunks * h * b
        + mechanism_cost(model, shape)["eva_attn"]["bytes"]}
    flops = nbytes = 0.0
    for t, (lanes, clips, any_lane) in enumerate(
            zip(p["lanes"], p["clips"], p["steps"])):
        flops += lanes * step_token_flops(model, t)
        exact, pooled = attended(model, P + t)
        first = ((P + t) // window) * window     # the query's window begins
        shared = max(P - first, 0), min(pooled, chunks)
        own = exact - shared[0] + pooled - shared[1] + 1    # and its new pair
        nbytes += (any_lane * weight_bytes(model)
                   + L * 2 * h * b * (clips * sum(shared) + lanes * own)
                   + 2 * lanes * model["vocab_size"] * 4)
    return {"eval_prefill": {k: float(v) for k, v in prefill.items()},
            "eval_decode": {"flops": float(flops), "bytes": float(nbytes)}}
