"""Cost model of the latent-attention, routed-expert caption decoder
(``configs/kimi_k2_ep32.json`` names it under ``costs``): operations and bytes
of its beam-search decode, from the configuration's ``model`` sizes and the
captions that ran. Written from the layer equations
(``reference_latent_moe.py``), not from the program.

Conventions (``cost_models/lstm_captioner.py`` has the same): FLOPs count
matrix multiplications only, ``2*m*n*k``. What is counted is the work the
captions need (``costs.caption_profile``): a step ``t`` costs the token FLOPs,
the cache traffic and the logits of the lanes that still hold a token at
``t``, and the weights once if any lane does; a step past the batch's longest
caption costs nothing, whether the program runs it or not.

- **Prefill**, once a clip: the frame projector and every prefix slot (all
  ``n_prefix`` of them: the profile does not say which frames are missing)
  through the stack with causal attention in the expanded form (keys and
  values through ``kv_b_proj`` once a slot). The last layer's FFN over the
  prefix feeds nothing and is not counted. Weights read once a batch, the
  features read, the compressed cache written.
- **A step**, for every lane that holds a token: the token through the stack
  with the absorbed attention over the ``n_prefix + t + 1`` positions it
  sees (``q_nope`` through ``kv_b_proj``'s key half, scores and values
  against the ``kv_lora_rank + rope`` cache, the value half once), the dense
  layers' FFN, an expert layer's router and shared experts, and its held
  experts **by expectation**: a token's ``num_experts_per_tok`` choices fall
  on this chip's ``experts_held`` of ``n_routed_experts`` with probability
  ``experts_held / n_routed_experts`` each (uniform routing; the run's own
  share is the per-layer metric ``moe_local_assignment_share``), then the
  head over the vocabulary slice.
- **A step's bytes**: every weight the chip holds read once if any lane holds
  a token, all held experts included (a step of 1280 lanes reaches each of
  them almost surely); each lane's cache read over the positions it sees and
  one position written, in every layer; the ``[lanes, V]`` float32 logits
  written and read once. The beam's reordering copy of the cache is the
  program's own and is not counted.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}


def n_prefix(model: dict) -> int:
    return len(model["modalities"]) * model["max_frames"]


def _layers(model: dict) -> tuple[int, int]:
    dense = min(model["first_k_dense_replace"], model["num_hidden_layers"])
    return dense, model["num_hidden_layers"] - dense


def attention_weights(model: dict) -> int:
    h, H = model["hidden_size"], model["num_attention_heads"]
    nope, rot, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    rank, q = model["kv_lora_rank"], model["q_lora_rank"]
    return (h * q + q * H * (nope + rot) + h * (rank + rot)
            + rank * H * (nope + vd) + H * vd * h)


def dense_ffn_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_ffn_weights(model: dict) -> int:
    """Router, shared experts and every held expert of one expert layer."""
    h, m = model["hidden_size"], model["moe_intermediate_size"]
    return h * model["n_routed_experts"] + 3 * h * m * (
        model["n_shared_experts"] + model["experts_held"])


def held_share(model: dict) -> float:
    """Expected held experts a token reaches in one expert layer."""
    return (model["num_experts_per_tok"] * model["experts_held"]
            / model["n_routed_experts"])


def _ffn_flops(model: dict) -> tuple[float, float]:
    """(a dense layer's, an expert layer's) FFN FLOPs per token."""
    h, m = model["hidden_size"], model["moe_intermediate_size"]
    return (2.0 * dense_ffn_weights(model),
            2.0 * h * model["n_routed_experts"]
            + 2.0 * 3 * h * m * (model["n_shared_experts"] + held_share(model)))


def _projection_flops(model: dict) -> float:
    """Per token and layer: the low-rank query pair, the compressed
    key/value projection and the output projection."""
    h, H = model["hidden_size"], model["num_attention_heads"]
    nope, rot, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    rank, q = model["kv_lora_rank"], model["q_lora_rank"]
    return 2.0 * (h * q + q * H * (nope + rot) + h * (rank + rot) + H * vd * h)


def step_token_flops(model: dict, context: int) -> float:
    """One decoded token seeing ``context`` positions, head included."""
    H = model["num_attention_heads"]
    nope, rot, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    rank = model["kv_lora_rank"]
    absorbed = 2.0 * H * rank * (nope + vd) \
        + 2.0 * context * H * ((rank + rot) + rank)
    dense, moe = _ffn_flops(model)
    n_dense, n_moe = _layers(model)
    return (model["num_hidden_layers"] * (_projection_flops(model) + absorbed)
            + n_dense * dense + n_moe * moe
            + 2.0 * model["hidden_size"] * model["vocab_size"])


def prefill_clip_flops(model: dict) -> float:
    """One clip's prefix through the stack, expanded causal attention; the
    last layer's FFN is left out."""
    H = model["num_attention_heads"]
    nope, rot, vd = (model["qk_nope_head_dim"], model["qk_rope_head_dim"],
                     model["v_head_dim"])
    rank, P = model["kv_lora_rank"], n_prefix(model)
    feat = sum(d for _, d in model["modalities"])
    expand = 2.0 * rank * H * (nope + vd)
    attend = sum(2.0 * (p + 1) * H * ((nope + rot) + vd) for p in range(P))
    dense, moe = _ffn_flops(model)
    n_dense, n_moe = _layers(model)
    last_dense = n_moe == 0
    ffn = (n_dense - last_dense) * dense + (n_moe - (not last_dense)) * moe
    return (2.0 * model["max_frames"] * feat * model["hidden_size"]
            + model["num_hidden_layers"] * (
                P * (_projection_flops(model) + expand) + attend)
            + P * ffn)


def stack_weight_bytes(model: dict, prefill: bool = False) -> float:
    """Bytes of the weights one pass reads: the stack, and for a step the
    head; for the prefill the projector, without the last layer's FFN."""
    b = _BYTES[model["param_dtype"]]
    n_dense, n_moe = _layers(model)
    h = model["hidden_size"]
    total = model["num_hidden_layers"] * attention_weights(model) \
        + n_dense * dense_ffn_weights(model) + n_moe * expert_ffn_weights(model)
    if prefill:
        total -= expert_ffn_weights(model) if n_moe else dense_ffn_weights(model)
        total += sum(d for _, d in model["modalities"]) * h
    else:
        total += h * model["vocab_size"]
    return float(b * total)


def cache_row_bytes(model: dict) -> int:
    """One position of one lane in one layer: ``[c_kv | k_r]``."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) \
        * _BYTES[model["dtype"]]


def full_profile(T: int, B: int, lanes: int) -> dict:
    """The profile of a batch whose every caption is ``T`` long
    (``cost_models/lstm_captioner.py`` documents the keys)."""
    return {"lanes": [float(lanes)] * T, "clips": [float(B)] * T,
            "steps": [1.0] * T}


def program_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"eval_decode": {"flops", "bytes"}}`` per decoded batch on ONE chip's
    share. ``shape``: ``{"kind": "eval", "B", "beam"}`` with an optional
    ``"profile"``; the configuration's cells run no other job."""
    if shape["kind"] != "eval":
        raise ValueError(
            f"the latent-attention decoder is costed for job eval alone, not "
            f"{shape['kind']!r}: its configuration has no training cell")
    T, B, L = model["max_len"], shape["B"], model["num_hidden_layers"]
    p = shape.get("profile") or full_profile(T, B, shape.get("beam", 1) * B)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    P, row = n_prefix(model), cache_row_bytes(model)
    feat = sum(d for _, d in model["modalities"])
    flops = B * prefill_clip_flops(model)
    nbytes = (stack_weight_bytes(model, prefill=True)
              + B * model["max_frames"] * feat * 4 + B * L * P * row)
    for t, (lanes, any_lane) in enumerate(zip(p["lanes"], p["steps"])):
        seen = P + t + 1
        flops += lanes * step_token_flops(model, seen)
        nbytes += (any_lane * stack_weight_bytes(model)
                   + lanes * L * (seen + 1) * row
                   + 2 * lanes * model["vocab_size"] * 4)
    return {"eval_decode": {"flops": float(flops), "bytes": float(nbytes)}}
