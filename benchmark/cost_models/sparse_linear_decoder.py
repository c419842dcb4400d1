"""Cost model of the sparse-softmax / linear-attention caption decoder
(``configs/minicpm_sala_8l.json`` names it under ``costs``): operations and
bytes of its beam-search evaluation, from the configuration's ``model`` sizes
and the captions that ran. Written from the layer equations
(``reference_sparse_linear.py``), not from the program: what is counted is the
work the model needs, whatever implements it.

Conventions (``cost_models/lstm_captioner.py`` has the same): FLOPs count
matrix multiplications only, ``2*m*n*k``; a step ``t`` costs the token FLOPs,
the state traffic and the logits of the lanes that still hold a token at
``t``, and the weights once if any lane does; a step past the batch's longest
caption costs nothing, whether the program runs it or not.

The program runs a batch as two programs and so does this model:
``eval_prefill`` (the video prefix through the stack, once a clip) and
``eval_decode`` (the beam search from it: what job ``eval`` hands over as the
compiled decode, and what ``eval_decode_device_ms_per_step`` and
``eval_decode_roofline`` read). ``mfu_end_to_end`` sums both.

- **Prefix**, once a clip, over all ``max_frames`` slots a modality (the
  profile does not say which slots are missing: a corpus whose clips hold
  fewer counts up to that share too much here, which the configuration's file
  states): the projector; every layer's q/k/v/gate/output projections and FFN,
  except that the last layer leaves only its keys and values (or state) and
  runs no mixer output and no FFN. A sparse layer's queries: under
  ``dense_len`` keys seen, all of them; from there on the selection's scores
  over the compressed keys seen and the attention over the keys the rule
  gives, **by expectation under a uniform choice**: the window, the first
  blocks, and of the ``topk`` chosen blocks those that fall outside both
  (the run's own share is the per-layer metric ``sparse_selected_key_share``).
  A linear layer: the recurrence, ``2 d^2`` to update a head's state and
  ``2 d^2`` to read it a position, however the program chunks it.
- **A step**, for every lane that holds a token: the same a position at
  ``max_frames + t + 1`` keys seen, and the head over the vocabulary.
- **Bytes.** Prefix: the weights it uses once a batch, the features read, the
  sparse layers' keys, values and compressed keys and the linear layers'
  states written; each mixer's q/k/v read and output written. A step: every
  weight once if any lane holds a token; a lane's attended keys and values
  and the compressed keys read in each sparse layer, its state read and
  written in each linear layer, the ``[lanes, V]`` float32 logits written and
  read once. The beam's reordering copy of its state is the program's own.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4}
SPARSE, LINEAR = "minicpm4", "lightning-attn"


def n_prefix(model: dict) -> int:
    return len(model["modalities"]) * model["max_frames"]


def _sizes(model: dict, kind: str) -> tuple[int, int, int]:
    """(heads, key/value heads, head size) of a layer of ``kind``."""
    if kind == SPARSE:
        return (model["num_attention_heads"], model["num_key_value_heads"],
                model["head_dim"])
    return model["lightning_nh"], model["lightning_nh"], model["lightning_head_dim"]


def mixer_weights(model: dict, kind: str, kv_only: bool = False) -> int:
    h = model["hidden_size"]
    H, G, d = _sizes(model, kind)
    if kv_only:
        return 2 * h * G * d
    return h * (2 * H * d + 2 * G * d) + H * d * h      # q, gate, k, v, output


def ffn_weights(model: dict) -> int:
    return 3 * model["hidden_size"] * model["intermediate_size"]


def parameter_count(model: dict) -> int:
    """Matrices only (the norms' vectors are 0.3 M of 2.82 B)."""
    h = model["hidden_size"]
    feat = sum(d for _, d in model["modalities"])
    return (sum(mixer_weights(model, k) + ffn_weights(model)
                for k in model["mixer_types"])
            + 2 * h * model["vocab_size"] + feat * h)


def attended_keys(model: dict, seen: int) -> float:
    """Keys a sparse layer's query attends to of the ``seen`` it sees."""
    if seen < model["sparse_dense_len"]:
        return float(seen)
    block = model["sparse_block_size"]
    blocks = -(-seen // block)
    fixed = min(model["sparse_window_size"] + model["sparse_init_blocks"] * block,
                seen)
    outside = max(blocks - fixed / block, 0.0) / blocks
    return min(fixed + model["sparse_topk"] * block * outside, float(seen))


def compressed_seen(model: dict, seen: int, limit: int) -> int:
    """Compressed keys a query scores: windows wholly among the keys it sees
    and among the prefix's ``limit`` positions; none under ``dense_len``."""
    if seen < model["sparse_dense_len"]:
        return 0
    span = min(seen, limit) - model["sparse_kernel_size"]
    return max(span // model["sparse_kernel_stride"] + 1, 0)


def mixer_position_flops(model: dict, kind: str, seen: int, limit: int) -> float:
    """One position's mixing (no projection) at ``seen`` keys seen."""
    H, _G, d = _sizes(model, kind)
    if kind == LINEAR:
        return 4.0 * H * d * d
    return 2.0 * H * d * (compressed_seen(model, seen, limit)
                          + 2 * attended_keys(model, seen))


def prefill_mixer_flops(model: dict, kind: str) -> float:
    """One clip's prefix through one layer's mixer."""
    P = n_prefix(model)
    if kind == LINEAR:
        return P * mixer_position_flops(model, kind, 0, P)
    return sum(mixer_position_flops(model, kind, p + 1, P) for p in range(P))


def _runs_mixer(model: dict) -> list[bool]:
    """Whether each layer's mixer output and FFN run over the prefix: all but
    the last layer's."""
    n = len(model["mixer_types"])
    return [i + 1 < n for i in range(n)]


def prefill_clip_flops(model: dict) -> float:
    P, h = n_prefix(model), model["hidden_size"]
    feat = sum(d for _, d in model["modalities"])
    flops = 2.0 * model["max_frames"] * feat * h
    for kind, runs in zip(model["mixer_types"], _runs_mixer(model)):
        if runs:
            flops += P * 2.0 * (mixer_weights(model, kind) + ffn_weights(model))
            flops += prefill_mixer_flops(model, kind)
        else:
            flops += P * 2.0 * mixer_weights(model, kind, kv_only=True)
            if kind == LINEAR:      # its state is what the caption reads
                H, _G, d = _sizes(model, kind)
                flops += P * 2.0 * H * d * d
    return flops


def step_token_flops(model: dict, t: int) -> float:
    """One decoded token at caption position ``t``, head included."""
    P = n_prefix(model)
    flops = 2.0 * model["hidden_size"] * model["vocab_size"]
    for kind in model["mixer_types"]:
        flops += 2.0 * (mixer_weights(model, kind) + ffn_weights(model)) \
            + mixer_position_flops(model, kind, P + t + 1, P)
    return flops


def weight_bytes(model: dict, prefill: bool = False) -> float:
    """Bytes of the weights one pass reads: a step's the stack and the
    head; the prefix's the projector and what its layers run."""
    b = _BYTES[model["param_dtype"]]
    h = model["hidden_size"]
    if not prefill:
        return float(b * (h * model["vocab_size"] + sum(
            mixer_weights(model, k) + ffn_weights(model)
            for k in model["mixer_types"])))
    total = sum(d for _, d in model["modalities"]) * h
    for kind, runs in zip(model["mixer_types"], _runs_mixer(model)):
        total += mixer_weights(model, kind) + ffn_weights(model) if runs \
            else mixer_weights(model, kind, kv_only=True)
    return float(b * total)


def mechanism_cost(model: dict, shape: dict) -> dict[str, dict[str, float]]:
    """``{"sparse_attn", "linear_attn"}``: operations and bytes of each
    mechanism over the prefix of one batch (the layers whose mixer runs
    there): what the two kernels' roofline shares are taken against. Bytes: a
    layer reads q, k, v and writes its output once; the sparse layer also
    reads the compressed keys."""
    B, P = shape["B"], n_prefix(model)
    b = _BYTES[model["dtype"]]
    out = {"sparse_attn": {"flops": 0.0, "bytes": 0.0},
           "linear_attn": {"flops": 0.0, "bytes": 0.0}}
    for kind, runs in zip(model["mixer_types"], _runs_mixer(model)):
        if not runs:
            continue
        H, G, d = _sizes(model, kind)
        cost = out["linear_attn" if kind == LINEAR else "sparse_attn"]
        cost["flops"] += B * prefill_mixer_flops(model, kind)
        cost["bytes"] += B * P * (2 * H + 2 * G) * d * b
        if kind == LINEAR:
            cost["bytes"] += B * H * d * d * 4
        else:
            cost["bytes"] += B * (P // model["sparse_kernel_stride"]) * G * d * 4
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
            f"the sparse/linear decoder is costed for job eval alone, not "
            f"{shape['kind']!r}: its configuration has no training cell")
    T, B = model["max_len"], shape["B"]
    p = shape.get("profile") or full_profile(T, B, shape.get("beam", 1) * B)
    if len(p["lanes"]) != T:
        raise ValueError(f"the profile has {len(p['lanes'])} steps, the "
                         f"model {T}")
    P, b = n_prefix(model), _BYTES[model["dtype"]]
    feat = sum(d for _, d in model["modalities"])
    kept = 0.0          # what the prefix leaves the caption, a clip
    for kind in model["mixer_types"]:
        H, G, d = _sizes(model, kind)
        kept += H * d * d * 4 if kind == LINEAR else \
            2 * P * G * d * b + (P // model["sparse_kernel_stride"]) * G * d * 4
    mech = mechanism_cost(model, shape)
    prefill = {
        "flops": B * prefill_clip_flops(model),
        "bytes": weight_bytes(model, prefill=True)
        + B * model["max_frames"] * feat * 4 + B * kept
        + sum(m["bytes"] for m in mech.values())}
    flops = nbytes = 0.0
    for t, (lanes, any_lane) in enumerate(zip(p["lanes"], p["steps"])):
        flops += lanes * step_token_flops(model, t)
        state = 0.0     # a lane's reads and writes of state at step t
        for kind in model["mixer_types"]:
            H, G, d = _sizes(model, kind)
            if kind == LINEAR:
                state += 2 * H * d * d * 4
            else:
                state += 2 * attended_keys(model, P + t + 1) * G * d * b \
                    + compressed_seen(model, P + t + 1, P) * G * d * 4
        nbytes += (any_lane * weight_bytes(model) + lanes * state
                   + 2 * lanes * model["vocab_size"] * 4)
    return {"eval_prefill": {k: float(v) for k, v in prefill.items()},
            "eval_decode": {"flops": float(flops), "bytes": float(nbytes)}}
