"""Cost model of the compressed-convolutional-attention, top-1-expert caption
decoder (``configs/zaya1_8b_20l.json`` names it under ``costs``): operations
and bytes of its beam-search evaluation, from the configuration's ``model``
sizes and the captions that ran. Written from the layer equations
(``reference_cca_moe.py``), not from the program: what is counted is the work
the model needs on ONE chip's share (here: the whole pipeline stage, every
expert held), the least a right program does, whatever implements it.

Conventions (``cost_models/lstm_captioner.py`` has the same): FLOPs count
matrix multiplications only, ``2*m*n*k`` (the grouped convolution's two taps
a head are such products; the depthwise one, the means, norms and rope are
not); a step ``t`` costs the token FLOPs, the state traffic and the logits of
the lanes that still hold a token at ``t``, and the weights once if any lane
does; a step past the batch's longest caption costs nothing, whether the
program runs it or not.

The program runs a batch as two programs and so does this model:
``eval_prefill`` (the video prefix through the stack, once a clip) and
``eval_decode`` (the beam search from it). ``mfu_end_to_end`` sums both.

- **The pairs.** A query at position ``i`` attends ``i + 1`` keys in every
  layer, ``2 H (2 head_dim)`` FLOPs a pair (scores and values, all heads, in
  the latent): the pairs under the diagonal and no other, so a kernel that
  walks whole tiles does more than is counted here and reads under 100 %.
- **The experts** by expectation: a token's one choice falls on each of the
  router's ``n_routed_experts + 1`` outputs alike, the last being no expert,
  so ``experts_held / (n_routed_experts + 1)`` experts a token a layer (16/17
  with every expert held; the run's own share that chose none is the
  per-layer metric ``moe_skip_share``: above 1/17 this count is an over-,
  under it an under-reading). The router: its down-projection and its three
  MLP products.
- **Prefix**, once a clip, over all ``max_frames`` slots a modality (the
  profile does not say which slots are missing: a corpus whose clips hold
  fewer counts up to that share too much here, quadratically in the pairs,
  which the configuration's file states): the projector; every layer's
  projections, mixing, router, expert and pairs, except that the last layer
  leaves only its keys, values and tail and runs no attention, no output
  projection, no router and no expert.
- **A step**, for every lane that holds a token: the same a position at
  ``max_frames + t``, and the tied head over the vocabulary.
- **Bytes.** Prefix: the weights it uses once a batch (every held expert: a
  prefix of 16 k rows reaches each), the features read, the keys and values
  the caption will read written; each layer's q, k, v read and output
  written. A step: the attention, router and head weights once if any lane
  holds a token, and of each layer's held experts **those a step of that many
  lanes reaches by expectation** (``held (1 - (1 - 1 / (n + 1)) ^ lanes)``:
  7.3 of 16 at 10 lanes; an expert no lane chose is not read); a layer's
  prefix keys and values in the latent **once a clip that holds a token**; a
  lane's own caption keys and its convolution tail read and the new ones
  written; the ``[lanes, V]`` float32 logits written and read once. The
  beam's reordering copy of its state is the program's own.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def n_prefix(model: dict) -> int:
    return len(model["modalities"]) * model["max_frames"]


def latent(model: dict) -> tuple[int, int]:
    """(the channels of q~ and k~ side by side, those of a value)."""
    d = model["head_dim"]
    return ((model["num_attention_heads"] + model["num_key_value_heads"]) * d,
            model["num_key_value_heads"] * d)


def attention_weights(model: dict, kv_only: bool = False) -> int:
    """A layer's projections into the latent (q~, k~ and the value's two
    halves), the grouped convolution's two taps a head and the output
    projection; ``kv_only`` what the prefix's last layer runs (no output
    projection)."""
    h, H, d = model["hidden_size"], model["num_attention_heads"], model["head_dim"]
    C, Cv = latent(model)
    into = h * (C + Cv) + 2 * (C // d) * d * d
    return into if kv_only else into + H * d * h


def router_weights(model: dict) -> int:
    R = model["router_hidden_size"]
    return model["hidden_size"] * R + 2 * R * R + R * (model["n_routed_experts"] + 1)


def expert_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def held_share(model: dict) -> float:
    """Expected held experts a token reaches in one layer."""
    return model["experts_held"] / (model["n_routed_experts"] + 1)


def experts_reached(model: dict, rows: float) -> float:
    """Expected held experts that ``rows`` tokens of one step reach."""
    miss = 1.0 - 1.0 / (model["n_routed_experts"] + 1)
    return model["experts_held"] * (1.0 - miss ** rows)


def parameter_count(model: dict) -> int:
    """Every parameter ``model.init`` declares: matrices, the depthwise
    convolution, norms, biases, temperatures, the router's scalars and the
    merge vectors; the embedding once (it is the head)."""
    h, G = model["hidden_size"], model["num_key_value_heads"]
    R, E = model["router_hidden_size"], model["n_routed_experts"]
    C, _ = latent(model)
    feat = sum(d for _, d in model["modalities"])
    small = 2 * C + 2 * C + G + 2 * h + 8 * h \
        + 1 + R + 2 * R + 2 * (E + 1)
    layer = attention_weights(model) + router_weights(model) + small \
        + model["experts_held"] * expert_weights(model)
    return model["num_hidden_layers"] * layer + model["vocab_size"] * h \
        + feat * h + h


def prefix_pairs(positions: int) -> int:
    """Query-key pairs of the queries at positions ``0 .. positions`` in one
    layer."""
    return positions * (positions + 1) // 2


def pair_flops(model: dict) -> float:
    """Scores and values of one query-key pair, all heads."""
    return 2.0 * model["num_attention_heads"] * 2 * model["head_dim"]


def layer_token_flops(model: dict) -> float:
    """A layer's FLOPs a token without its pairs: projections, mixing,
    router, and one expert at the expected share."""
    return 2.0 * (attention_weights(model) + router_weights(model)
                  + expert_weights(model) * held_share(model))


def prefill_clip_flops(model: dict) -> float:
    P, L = n_prefix(model), model["num_hidden_layers"]
    feat = sum(d for _, d in model["modalities"])
    return 2.0 * model["max_frames"] * feat * model["hidden_size"] \
        + (L - 1) * (P * layer_token_flops(model)
                     + pair_flops(model) * prefix_pairs(P)) \
        + P * 2.0 * attention_weights(model, kv_only=True)


def step_token_flops(model: dict, t: int) -> float:
    """One decoded token at caption position ``t``, head included."""
    at = n_prefix(model) + t
    return 2.0 * model["hidden_size"] * model["vocab_size"] \
        + model["num_hidden_layers"] * (
            layer_token_flops(model) + pair_flops(model) * (at + 1))


def weight_bytes(model: dict, rows: float | None = None) -> float:
    """Bytes of the weights one pass reads. A step of ``rows`` lanes: the
    stack (of a layer's held experts those the rows reach), and the embedding
    as the head. The prefix (``rows`` None): the projector and what its
    layers run, every held expert."""
    b = _BYTES[model["param_dtype"]]
    h, L = model["hidden_size"], model["num_hidden_layers"]
    if rows is None:
        total = sum(d for _, d in model["modalities"]) * h \
            + attention_weights(model, kv_only=True)
        layers, reached = L - 1, model["experts_held"]
    else:
        total, layers = h * model["vocab_size"], L
        reached = experts_reached(model, rows)
    total += layers * (attention_weights(model) + router_weights(model)
                       + reached * expert_weights(model))
    return float(b * total)


def kv_row_bytes(model: dict) -> int:
    """One position's key and value in one layer, in the latent."""
    return 2 * latent(model)[1] * _BYTES[model["dtype"]]


def tail_bytes(model: dict) -> int:
    """A lane's convolution tail in one layer: ``c``, ``a`` and the value's
    late half."""
    C, Cv = latent(model)
    return (2 * C + Cv // 2) * _BYTES[model["dtype"]]


def mechanism_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"cca_attn"}``: operations and bytes of the latent attention over
    the prefix of one batch (the layers whose queries run there: not the
    last): what the kernel's roofline share is taken against. The pairs under
    the diagonal and no other; a layer reads q, k, v and writes its output
    once."""
    B, P = shape["B"], n_prefix(model)
    runs = model["num_hidden_layers"] - 1
    H, d = model["num_attention_heads"], model["head_dim"]
    b = _BYTES[model["dtype"]]
    return {"cca_attn": {
        "flops": float(B * runs * pair_flops(model) * prefix_pairs(P)),
        "bytes": float(B * runs * P * (2 * H * d * b + kv_row_bytes(model)))}}


def full_profile(T: int, B: int, lanes: int) -> dict:
    return {"lanes": [float(lanes)] * T, "clips": [float(B)] * T,
            "steps": [1.0] * T}


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"eval_prefill", "eval_decode"}``, each ``{"flops", "bytes"}`` per
    decoded batch on ONE chip's share. ``shape``: ``{"kind": "eval", "B",
    "beam"}`` with an optional ``"profile"``."""
    if shape["kind"] != "eval":
        raise ValueError(
            f"the compressed-latent decoder is costed for job eval alone, "
            f"not {shape['kind']!r}: its configuration has no training cell")
    T, B, L = model["max_len"], shape["B"], model["num_hidden_layers"]
    p = shape.get("profile") or full_profile(T, B, shape.get("beam", 1) * B)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    P = n_prefix(model)
    feat = sum(d for _, d in model["modalities"])
    shared = L * P * kv_row_bytes(model)    # a clip's prefix, every layer
    prefill = {
        "flops": B * prefill_clip_flops(model),
        "bytes": weight_bytes(model) + B * model["max_frames"] * feat * 4
        + B * shared + mechanism_cost(model, shape)["cca_attn"]["bytes"]}
    flops = nbytes = 0.0
    own_row = L * kv_row_bytes(model)
    for t, (lanes, clips, any_lane) in enumerate(
            zip(p["lanes"], p["clips"], p["steps"])):
        flops += lanes * step_token_flops(model, t)
        nbytes += (any_lane * weight_bytes(model, rows=lanes)
                   + clips * shared
                   + lanes * ((t + 2) * own_row + 2 * L * tail_bytes(model))
                   + 2 * lanes * model["vocab_size"] * 4)
    return {"eval_prefill": {k: float(v) for k, v in prefill.items()},
            "eval_decode": {"flops": float(flops), "bytes": float(nbytes)}}
