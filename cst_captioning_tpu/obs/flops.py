"""Analytic matmul-FLOP cost models + the chip peak table (pure stdlib).

One source of truth for the numbers its consumers would otherwise duplicate:

- the trainer / SCST loop — per-step ``flops.<phase>`` counters feeding the
  run report's MFU column (``obs/report.py``);
- ``cli.obs_report`` — which must aggregate WITHOUT importing jax, hence
  everything here is plain arithmetic over ints.

Conventions: FLOPs count matmuls only as ``2*m*n*k`` — elementwise/softmax
work is ignored (the model is matmul-dominated); the backward pass is taken
as 2x the forward (3x overall). ``E`` below is the encoder output dim
(== ``d_embed``: every modality is embedded to ``d_embed`` and concatenated
on the frame axis, so ``M = n_modalities * F``).
"""

from __future__ import annotations

# peak dense bf16 FLOP/s per chip by device kind (public TPU specs); the
# match is substring-based. A kind that is not in the table has no peak: a
# utilization against a guessed peak is a number about nothing, so the
# lookup raises instead of defaulting
PEAK_BF16_FLOPS = (
    ("v6e", 918e12), ("v6 lite", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12), ("v5 lite", 197e12), ("v5litepod", 197e12),
    ("v4", 275e12),
)


def peak_flops(device_kind: str) -> float:
    """Published peak dense bf16 FLOP/s for a ``device_kind`` string;
    raises ``KeyError`` for a kind the table does not hold (e.g. "cpu")."""
    kind = device_kind.lower()
    for frag, peak in PEAK_BF16_FLOPS:
        if frag in kind:
            return peak
    raise KeyError(
        f"no published peak for device kind {device_kind!r} — utilization "
        "against it is not measured (add the chip to obs/flops.py with its "
        "source to measure it)"
    )


def enc_and_per_tok_flops(
    F: int, d_embed: int, d_hidden: int, d_att: int, V: int,
    feat_dims: tuple[int, ...], num_layers: int = 1,
) -> tuple[float, float]:
    """(encoder-pass, per-decoded-token) matmul FLOPs of the caption model.

    Encoder: per-modality frame embeddings + the attention memory-key
    projection. Per token: additive attention (query proj, scores, context
    sum over the M-slot concat memory), the input-feed LSTM stack (layer 0
    input is ``[word_emb, ctx]`` = ``2*d_embed``), and the output
    projection.
    """
    M = len(feat_dims) * F
    E, H, A = d_embed, d_hidden, d_att
    enc = 2 * F * sum(feat_dims) * E + 2 * M * E * A
    lstm = 2 * (E + E) * (4 * H) + 2 * H * (4 * H)        # layer 0
    lstm += (num_layers - 1) * (2 * H * (4 * H) + 2 * H * (4 * H))
    per_tok = (
        2 * H * A          # attention query projection
        + 2 * M * A        # scores
        + 2 * M * E        # context weighted sum
        + lstm
        + 2 * H * V        # output projection
    )
    return float(enc), float(per_tok)


def stride_steps(T: int, stride: int = 1) -> int:
    """Scan-step budget of a strided decode loop: T rounded up to the next
    stride multiple (the driving loop advances whole strides, so the final
    partial chunk still steps ``stride`` times)."""
    s = max(int(stride), 1)
    return -(-int(T) // s) * s


def decode_flops_per_clip(
    K: int, T: int, F: int, d_embed: int, d_hidden: int, d_att: int, V: int,
    feat_dims: tuple[int, ...], num_layers: int = 1,
    with_greedy: bool = True, fused: bool = True,
    stride: int = 1, active_frac: float = 1.0,
) -> float:
    """Matmul FLOPs of one RL decode per clip.

    ``fused=True`` (the one-loop default, PR 4): ONE encoder pass feeds both
    the greedy lane and the K sampled lanes. ``fused=False`` is the two-loop
    reference: greedy and sampling each run their own encoder pass.

    ``stride`` rounds the step budget up to whole driving-loop chunks
    (``decode_stride``); ``active_frac`` scales the per-token work by the
    fraction of lane-steps actually computed — 1.0 assumes every lane steps
    the full budget (the uncompacted worst case), while a measured value
    from the ``rl.decode.compaction`` counters (lanes_stepped /
    (lanes_stepped + lanes_skipped)) gives the compaction-aware cost.
    """
    enc, per_tok = enc_and_per_tok_flops(
        F, d_embed, d_hidden, d_att, V, feat_dims, num_layers
    )
    lanes = (1 if with_greedy else 0) + K
    enc_passes = 1 if (fused or not with_greedy) else 2
    steps = stride_steps(T, stride)
    return float(enc_passes * enc + lanes * steps * per_tok * active_frac)


def serving_bank_bytes_per_stride(
    rows: int, width_slots: int, d_embed: int, d_att: int,
    dtype_bytes: int = 4, paged: bool = False,
) -> float:
    """Encoder-bank HBM bytes one serving stride moves, per decode path.

    The bank is ``rows`` lanes x ``width_slots`` memory slots of
    ``(E mem + A proj + 1 mask)`` elements. The dense-gather path pays it
    THREE times per stride: the gather reads the pool, writes the dense
    [B, W, *] bank, and the stride kernel reads the bank back. The paged
    in-kernel path DMAs each batch block's pages from the pool into VMEM
    exactly once — one read, no dense bank — so its cost is the bank bytes
    themselves. ``serving.gather_bytes_avoided`` counts the difference
    (2x the bank) per paged stride dispatch. One ``dtype_bytes`` covers
    all three pools (the mask pool is f32 even under a bf16 model — at
    bf16 this overstates mask traffic by 2 of ~E+A+1 elements; the model
    stays deliberately simple)."""
    bank = float(rows) * width_slots * (d_embed + d_att + 1) * dtype_bytes
    return bank if paged else 3.0 * bank


def update_flops_per_clip(
    K: int, T: int, F: int, d_embed: int, d_hidden: int, d_att: int, V: int,
    feat_dims: tuple[int, ...], num_layers: int = 1,
) -> float:
    """Matmul FLOPs of one REINFORCE update per clip: one encoder pass, K
    teacher-forced rollout rows, forward+backward as 3x forward."""
    enc, per_tok = enc_and_per_tok_flops(
        F, d_embed, d_hidden, d_att, V, feat_dims, num_layers
    )
    return float(3 * (enc + K * T * per_tok))


def xe_flops_per_row(
    T: int, F: int, d_embed: int, d_hidden: int, d_att: int, V: int,
    feat_dims: tuple[int, ...], num_layers: int = 1,
) -> float:
    """Matmul FLOPs of one teacher-forced XE row (forward+backward)."""
    enc, per_tok = enc_and_per_tok_flops(
        F, d_embed, d_hidden, d_att, V, feat_dims, num_layers
    )
    return float(3 * (enc + T * per_tok))


# ---- the latent-attention, routed-expert decoder (models/latent_moe.py) -------


def latent_moe_per_tok_flops(mc, context: int) -> float:
    """Matmul FLOPs one token costs in the ``decoder="latent_moe"`` stack
    with ``context`` positions to attend over, head excluded: the low-rank
    query and compressed key/value projections, attention in the absorbed
    form (scores and values against the ``kv_lora_rank + rope`` cache, the
    two halves of ``kv_b_proj`` once a token), the output projection, the
    dense layers' FFN, and an expert layer's router, shared experts and held
    experts by expectation (a token's ``num_experts_per_tok`` choices fall
    on this chip's ``experts_held`` of ``n_routed_experts`` uniformly)."""
    h, H = mc.hidden_size, mc.num_attention_heads
    nope, rot, vd = mc.qk_nope_head_dim, mc.qk_rope_head_dim, mc.v_head_dim
    rank = mc.kv_lora_rank
    attn = 2 * (h * mc.q_lora_rank + mc.q_lora_rank * H * (nope + rot)
                + h * (rank + rot) + H * nope * rank + H * rank * vd
                + H * vd * h)
    attn += 2 * context * H * ((rank + rot) + rank)
    dense = 2 * 3 * h * mc.intermediate_size
    held = mc.num_experts_per_tok * mc.experts_held / max(mc.n_routed_experts, 1)
    moe = 2 * h * mc.n_routed_experts + 2 * 3 * h * mc.moe_intermediate_size * (
        mc.n_shared_experts + held)
    n_dense = min(mc.first_k_dense_replace, mc.num_hidden_layers)
    return float(mc.num_hidden_layers * attn + n_dense * dense
                 + (mc.num_hidden_layers - n_dense) * moe)


# ---- the sparse-softmax / linear-attention decoder (models/sparse_linear.py) --


def sparse_linear_per_tok_flops(mc, context: int) -> float:
    """Matmul FLOPs one token costs in the ``decoder="sparse_linear"`` stack
    at ``context`` positions seen, head excluded: a layer's q/k/v/gate/output
    projections and gated FFN; a sparse layer's scores and values over the
    keys its rule attends to (all under ``sparse_dense_len``; else the
    ``sparse_topk`` blocks, the first and the window, at most the context)
    and its selection over the compressed keys; a linear layer's state
    update and read (``2 * 2 * head_dim^2`` a head)."""
    h, m = mc.hidden_size, mc.intermediate_size
    total = 0.0
    for kind in mc.mixer_types:
        if kind == "minicpm4":
            H, G, d = mc.num_attention_heads, mc.num_key_value_heads, mc.head_dim
            keys = context if context < mc.sparse_dense_len else min(
                context, (mc.sparse_topk + mc.sparse_init_blocks)
                * mc.sparse_block_size + mc.sparse_window_size)
            pooled = 0 if context < mc.sparse_dense_len else \
                context // mc.sparse_kernel_stride
            mix = 2 * 2 * H * d * keys + 2 * H * d * pooled
        else:
            H = G = mc.lightning_nh
            d = mc.lightning_head_dim
            mix = 2 * 2 * H * d * d
        total += 2 * h * (2 * H * d + 2 * G * d) + 2 * H * d * h + mix \
            + 2 * 3 * h * m
    return float(total)


# ---- the EVA-attention decoder (models/eva.py) ---------------------------------


def eva_per_tok_flops(mc, context: int) -> float:
    """Matmul FLOPs one token costs in the ``decoder="eva"`` stack at
    ``context`` positions seen (its own the last), head excluded: a layer's
    four projections and gated FFN, and its scores and values over the exact
    keys of its window and one summary a chunk of the windows before."""
    h, m = mc.hidden_size, mc.intermediate_size
    last = context - 1
    keys = last % mc.window_size + 1 \
        + (mc.window_size // mc.chunk_size) * (last // mc.window_size)
    return float(mc.num_hidden_layers * (
        2 * 4 * h * h + 2 * 3 * h * m + 2 * 2 * h * keys))


# ---- the window/full-attention, routed-expert decoder (models/window_moe.py) --


def window_moe_per_tok_flops(mc, context: int) -> float:
    """Matmul FLOPs one token costs in the ``decoder="window_moe"`` stack at
    ``context`` positions seen (its own the last), head excluded: a layer's
    four projections (keys of ``head_dim``, values of ``v_head_dim``, the
    key/value head count of its kind), its scores and values over the keys it
    attends (all of them in a full layer, the last ``sliding_window`` in a
    window layer), and its FFN: dense, or the router and the held experts by
    expectation (``latent_moe_per_tok_flops``'s convention)."""
    h, H, dk, dv = mc.hidden_size, mc.num_attention_heads, mc.head_dim, mc.v_head_dim
    held = mc.num_experts_per_tok * mc.experts_held / max(mc.n_routed_experts, 1)
    total = 0.0
    for i, kind in enumerate(mc.mixer_types):
        window = kind == "window"
        G = mc.swa_num_key_value_heads if window else mc.num_key_value_heads
        keys = min(context, mc.sliding_window) if window else context
        total += 2 * h * (H * dk + G * (dk + dv)) + 2 * H * dv * h \
            + 2 * H * (dk + dv) * keys
        if mc.first_layer_index + i < mc.first_k_dense_replace:
            total += 2 * 3 * h * mc.intermediate_size
        else:
            total += 2 * h * mc.n_routed_experts \
                + 2 * 3 * h * mc.moe_intermediate_size * held
    return float(total)


# ---- the compressed-latent, top-1-expert decoder (models/cca_moe.py) ----------


def cca_moe_per_tok_flops(mc, context: int) -> float:
    """Matmul FLOPs one token costs in the ``decoder="cca_moe"`` stack at
    ``context`` positions seen (its own the last), head excluded: a layer's
    projections into the latent and out of it, the grouped convolution's two
    taps a head, the scores and values over every key, the router (its
    down-projection and three MLP products) and one expert at the share of
    tokens that choose one of this chip's by expectation: ``experts_held`` of
    the router's ``n_routed_experts + 1`` outputs (the last is no expert)."""
    h, H, G, d = (mc.hidden_size, mc.num_attention_heads,
                  mc.num_key_value_heads, mc.head_dim)
    R, E = mc.router_hidden_size, mc.n_routed_experts
    attn = 2 * h * (H + 2 * G) * d + 2 * H * d * h \
        + 2 * (H + G) * 2 * d * d + 2 * H * 2 * d * context
    router = 2 * h * R + 2 * 2 * R * R + 2 * R * (E + 1)
    expert = 2 * 3 * h * mc.moe_intermediate_size * mc.experts_held / (E + 1)
    return float(mc.num_hidden_layers * (attn + router + expert))


def model_xe_flops_per_row(mc) -> float:
    """Matmul FLOPs of one teacher-forced XE row (forward + backward as 3x
    forward) of the model ``mc`` (a ``ModelConfig``) describes, by its
    decoder kind: what ``Trainer`` feeds the ``flops.xe.step`` counter."""
    feat_dims = tuple(d for _, d in mc.modalities)
    per_tok = {"latent_moe": latent_moe_per_tok_flops,
               "sparse_linear": sparse_linear_per_tok_flops,
               "eva": eva_per_tok_flops,
               "window_moe": window_moe_per_tok_flops,
               "cca_moe": cca_moe_per_tok_flops}.get(mc.decoder)
    if per_tok is not None:
        n_prefix = len(feat_dims) * mc.max_frames
        fwd = 2.0 * mc.max_frames * sum(feat_dims) * mc.hidden_size
        fwd += sum(per_tok(mc, p + 1) for p in range(n_prefix + mc.max_len))
        fwd += mc.max_len * 2.0 * mc.hidden_size * mc.vocab_size
        return float(3 * fwd)
    return xe_flops_per_row(
        T=mc.max_len, F=mc.max_frames, d_embed=mc.d_embed,
        d_hidden=mc.d_hidden, d_att=mc.d_att, V=mc.vocab_size,
        feat_dims=feat_dims, num_layers=mc.num_layers,
    )


# ---- XLA HLO cost-analysis backend ------------------------------------------
#
# The analytic counters above are matmul-only estimates; XLA's own HLO cost
# analysis counts the COMPILED program (every fused op, the real
# elementwise/softmax work, rematerialization). When a jitted callable and
# its example arguments are at hand — the SCST update does — prefer
# compiled-program FLOPs for the MFU ledger and fall back to the analytic
# model when the backend can't report them (interpret-mode Pallas calls,
# older runtimes, lowerings without cost data). jax imports stay INSIDE the
# function: this module must keep importing on jax-free boxes
# (cli.obs_report's contract).


def compiled_cost(fn, *args, **kwargs) -> dict | None:
    """``{"flops": float, "bytes_accessed": float}`` of ``jit(fn)(*args)``
    per XLA's HLO cost analysis, or None when unavailable (no jax, no
    backend cost model, analysis raises). ``fn`` may already be jitted
    (anything with ``.lower``)."""
    try:
        import jax
    except Exception:  # pragma: no cover - jax-free box
        return None
    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        analysis = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(analysis, (list, tuple)):
            analysis = analysis[0] if analysis else None
        if not analysis:
            return None
        flops = float(analysis.get("flops", 0.0) or 0.0)
        if flops <= 0.0:
            return None
        return {
            "flops": flops,
            "bytes_accessed": float(
                analysis.get("bytes accessed", 0.0) or 0.0
            ),
        }
    except Exception:
        # cost analysis is best-effort by contract: any backend refusal
        # degrades to the analytic model, never to a crash
        return None
