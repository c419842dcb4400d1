"""Cost model of the window/full-attention, routed-expert caption decoder
(``configs/mimo_v2_5_ep16.json`` names it under ``costs``): operations and
bytes of its beam-search evaluation, from the configuration's ``model`` sizes
and the captions that ran. Written from the layer equations
(``reference_window_moe.py``), not from the program: what is counted is the
work the model needs on ONE chip's share, the least a right program does,
whatever implements it.

Conventions (``cost_models/lstm_captioner.py`` has the same): FLOPs count
matrix multiplications only, ``2*m*n*k``; a step ``t`` costs the token FLOPs,
the state traffic and the logits of the lanes that still hold a token at
``t``, and the weights once if any lane does; a step past the batch's longest
caption costs nothing, whether the program runs it or not.

The program runs a batch as two programs and so does this model:
``eval_prefill`` (the video prefix through the stack, once a clip) and
``eval_decode`` (the beam search from it). ``mfu_end_to_end`` sums both.

- **The pairs.** A query at position ``i`` attends ``i + 1`` keys in a full
  layer and ``min(i + 1, sliding_window)`` in a window layer
  (:func:`attended`), ``2 H (head_dim + v_head_dim)`` FLOPs a pair (scores and
  values, all heads): the pairs inside the band or under the diagonal and no
  other, so a kernel that walks whole tiles, or attends densely where a
  window stands, does more than is counted here and reads under 100 %.
- **The held experts** by expectation, as ``latent_moe_decoder.py`` counts
  them: a token's ``num_experts_per_tok`` choices fall on this chip's
  ``experts_held`` of ``n_routed_experts`` uniformly, ``k held / n_routed``
  experts a token (the run's own share is the per-layer metric
  ``moe_local_assignment_share``); the router over all of them.
- **Prefix**, once a clip, over all ``max_frames`` slots a modality (the
  profile does not say which slots are missing: a corpus whose clips hold
  fewer counts up to that share too much here, quadratically in a full
  layer's pairs, which the configuration's file states): the projector; every
  layer's projections, FFN and pairs, except that the last layer leaves only
  its keys and values and runs no query, no output projection and no FFN.
- **A step**, for every lane that holds a token: the same a position at
  ``max_frames + t``, and the head over the vocabulary slice.
- **Bytes.** Prefix: the weights it uses once a batch (every held expert: a
  prefix of 16 k rows reaches each), the features read, the keys and values
  the caption will read written; each layer's q, k, v read and output written.
  A step: the attention, dense, router and head weights once if any lane
  holds a token, and of each expert layer's held experts **those a step of
  that many lanes reaches by expectation** (``held (1 - (1 - k / n_routed) ^
  lanes)``: 4.4 of 16 at 10 lanes; an expert no lane chose is not read); a
  full layer's prefix keys and values and a window layer's last window **once
  a clip that holds a token**; a lane's own caption keys read and its new pair
  written; the ``[lanes, V]`` float32 logits written and read once. The
  beam's reordering copy of its state is the program's own.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def n_prefix(model: dict) -> int:
    return len(model["modalities"]) * model["max_frames"]


def kinds(model: dict) -> list[tuple[bool, bool]]:
    """(window?, dense?) of each held layer."""
    return [(kind == "window",
             model["first_layer_index"] + i < model["first_k_dense_replace"])
            for i, kind in enumerate(model["mixer_types"])]


def kv_heads(model: dict, window: bool) -> int:
    return model["swa_num_key_value_heads" if window else "num_key_value_heads"]


def attention_weights(model: dict, window: bool, kv_only: bool = False) -> int:
    """A layer's projections; ``kv_only`` the two the prefix's last layer runs."""
    h, H = model["hidden_size"], model["num_attention_heads"]
    dk, dv, G = model["head_dim"], model["v_head_dim"], kv_heads(model, window)
    kv = h * G * (dk + dv)
    return kv if kv_only else kv + h * H * dk + H * dv * h


def dense_ffn_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_share(model: dict) -> float:
    """Expected held experts a token reaches in one expert layer."""
    return (model["num_experts_per_tok"] * model["experts_held"]
            / model["n_routed_experts"])


def experts_reached(model: dict, rows: float) -> float:
    """Expected held experts that ``rows`` tokens of one step reach."""
    miss = 1.0 - model["num_experts_per_tok"] / model["n_routed_experts"]
    return model["experts_held"] * (1.0 - miss ** rows)


def parameter_count(model: dict) -> int:
    """Every parameter ``model.init`` declares: matrices, norms, sinks and
    router biases."""
    h, H, E = (model["hidden_size"], model["num_attention_heads"],
               model["n_routed_experts"])
    feat = sum(d for _, d in model["modalities"])
    total = 2 * h * model["vocab_size"] + feat * h + h
    for window, dense in kinds(model):
        total += attention_weights(model, window) + 2 * h + (H if window else 0)
        total += dense_ffn_weights(model) if dense else (
            h * E + E + model["experts_held"] * expert_weights(model))
    return total


def attended(model: dict, position: int, window: bool) -> int:
    """Keys the query at ``position`` attends in a layer of that kind."""
    return min(position + 1, model["sliding_window"]) if window else position + 1


def prefix_pairs(model: dict, positions: int, window: bool) -> int:
    """Query-key pairs of the queries at positions ``0 .. positions`` in one
    layer: :func:`attended` summed, in closed form."""
    if not window:
        return positions * (positions + 1) // 2
    w = min(model["sliding_window"], positions)
    return w * (w + 1) // 2 + (positions - w) * w


def pair_flops(model: dict) -> float:
    """Scores and values of one query-key pair, all heads."""
    return 2.0 * model["num_attention_heads"] * (
        model["head_dim"] + model["v_head_dim"])


def ffn_flops(model: dict, dense: bool) -> float:
    """A layer's FFN FLOPs a token: the dense FFN, or the router and the
    expected held experts."""
    if dense:
        return 2.0 * dense_ffn_weights(model)
    return 2.0 * model["hidden_size"] * model["n_routed_experts"] \
        + 2.0 * expert_weights(model) * held_share(model)


def prefill_clip_flops(model: dict) -> float:
    P = n_prefix(model)
    feat = sum(d for _, d in model["modalities"])
    total = 2.0 * model["max_frames"] * feat * model["hidden_size"]
    layers = kinds(model)
    for window, dense in layers[:-1]:
        total += P * (2.0 * attention_weights(model, window)
                      + ffn_flops(model, dense)) \
            + pair_flops(model) * prefix_pairs(model, P, window)
    return total + P * 2.0 * attention_weights(model, layers[-1][0], kv_only=True)


def step_token_flops(model: dict, t: int) -> float:
    """One decoded token at caption position ``t``, head included."""
    at = n_prefix(model) + t
    return 2.0 * model["hidden_size"] * model["vocab_size"] + sum(
        2.0 * attention_weights(model, window) + ffn_flops(model, dense)
        + pair_flops(model) * attended(model, at, window)
        for window, dense in kinds(model))


def weight_bytes(model: dict, rows: float | None = None) -> float:
    """Bytes of the weights one pass reads. A step of ``rows`` lanes: the
    stack (of an expert layer's held experts those the rows reach) and the
    head. The prefix (``rows`` None): the projector and what its layers run,
    every held expert."""
    b = _BYTES[model["param_dtype"]]
    h = model["hidden_size"]
    layers = kinds(model)
    if rows is None:
        total = sum(d for _, d in model["modalities"]) * h \
            + attention_weights(model, layers[-1][0], kv_only=True)
        layers, reached = layers[:-1], model["experts_held"]
    else:
        total, reached = h * model["vocab_size"], experts_reached(model, rows)
    for window, dense in layers:
        total += attention_weights(model, window)
        total += dense_ffn_weights(model) if dense else (
            h * model["n_routed_experts"] + reached * expert_weights(model))
    return float(b * total)


def kv_row_bytes(model: dict, window: bool) -> int:
    """One position's key and value in one layer."""
    return kv_heads(model, window) * (model["head_dim"] + model["v_head_dim"]) \
        * _BYTES[model["dtype"]]


def mechanism_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"window_attn", "full_attn"}``: operations and bytes of the two
    kinds' attention over the prefix of one batch (the layers whose queries
    run there: not the last): what each kernel's roofline share is taken
    against. The pairs inside the band (or under the diagonal) and no other;
    a layer reads q, k, v and writes its output once."""
    B, P = shape["B"], n_prefix(model)
    H, dk, dv = model["num_attention_heads"], model["head_dim"], model["v_head_dim"]
    b = _BYTES[model["dtype"]]
    out = {}
    for name, window in (("window_attn", True), ("full_attn", False)):
        runs = sum(1 for w, _ in kinds(model)[:-1] if w == window)
        out[name] = {
            "flops": float(B * runs * pair_flops(model)
                           * prefix_pairs(model, P, window)),
            "bytes": float(B * runs * P * (
                H * (dk + dv) * b + kv_row_bytes(model, window)))}
    return out


def full_profile(T: int, B: int, lanes: int) -> dict:
    return {"lanes": [float(lanes)] * T, "clips": [float(B)] * T,
            "steps": [1.0] * T}


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"eval_prefill", "eval_decode"}``, each ``{"flops", "bytes"}`` per
    decoded batch on ONE chip's share. ``shape``: ``{"kind": "eval", "B",
    "beam"}`` with an optional ``"profile"``."""
    if shape["kind"] != "eval":
        raise ValueError(
            f"the window/full-attention decoder is costed for job eval alone, "
            f"not {shape['kind']!r}: its configuration has no training cell")
    T, B = model["max_len"], shape["B"]
    p = shape.get("profile") or full_profile(T, B, shape.get("beam", 1) * B)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    P = n_prefix(model)
    feat = sum(d for _, d in model["modalities"])
    layers = kinds(model)
    window = model["sliding_window"]

    def shared(t: int) -> float:
        """What a clip's lanes share and a step at caption position ``t``
        reads once a clip: a full layer's prefix, a window layer's prefix
        positions still inside the band."""
        return sum(kv_row_bytes(model, w)
                   * (min(max(window - 1 - t, 0), P) if w else P)
                   for w, _ in layers)

    prefill = {
        "flops": B * prefill_clip_flops(model),
        "bytes": weight_bytes(model) + B * model["max_frames"] * feat * 4
        + B * shared(0) + sum(c["bytes"] for c in
                           mechanism_cost(model, shape).values())}
    flops = nbytes = 0.0
    own_row = sum(kv_row_bytes(model, w) for w, _ in layers)
    for t, (lanes, clips, any_lane) in enumerate(
            zip(p["lanes"], p["clips"], p["steps"])):
        flops += lanes * step_token_flops(model, t)
        nbytes += (any_lane * weight_bytes(model, rows=lanes)
                   + clips * shared(t) + lanes * (t + 2) * own_row
                   + 2 * lanes * model["vocab_size"] * 4)
    return {"eval_prefill": {k: float(v) for k, v in prefill.items()},
            "eval_decode": {"flops": float(flops), "bytes": float(nbytes)}}
