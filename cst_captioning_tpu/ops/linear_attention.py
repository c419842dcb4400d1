"""Causal linear attention with a per-head decay (lightning attention): the
prefix as a chunked scan, a decode step as the one-step recurrence.

    S_t = lam_h * S_{t-1} + k_t^T v_t        (state [d, d] a head, float32)
    o_t = (q_t / sqrt(d)) S_t                lam_h = exp(-slope_h)

Only the first ``n`` positions of a row exist (the model compacts a clip's
valid slots to the front, models/sparse_linear.py): a position from ``n`` on
neither decays the state nor adds to it, and its output is never read.

The prefix (:func:`chunked_linear_attention`) walks ``chunk`` positions at a
time. With ``c_i`` the count of existing positions up to and including ``i``
inside the chunk, and ``c`` the chunk's whole count,

    o_i   = exp(-s c_i) (q_i / sqrt(d)) S_in
            + sum_{j <= i} exp(-s (c_i - c_j)) (q_i . k_j / sqrt(d)) v_j
    S_out = exp(-s c) S_in + sum_j exp(-s (c - c_j)) k_j^T v_j

Every exponent is of a difference taken first, so nothing leaves [0, 1]
whatever the slope (the fastest head forgets within a few positions: a
product ``(q e^{+sc}) (k e^{-sc})`` would overflow at chunk 256). The state is
float32 and enters a product in the operands' dtype, accumulated in float32.

``impl="pallas"`` runs the chunk walk as one kernel (``linear_attn_prefill``
in a device trace: grid rows x heads x chunks, the state in VMEM scratch
across the chunk axis); ``"xla"`` is the same arithmetic as a ``lax.scan``
and the parity oracle. Off the TPU the kernel runs in interpret mode.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def decay_slopes(n_heads: int, layer_index: int, n_layers: int) -> jnp.ndarray:
    """[H] float32 ``slope_h = 2^(-8 (h + 1) / H) * (1 - l / (L - 1) + 1e-5)``:
    the lightning-attention family's head slopes, scaled by the layer's depth
    ``l`` of ``L`` as that family scales them (deeper layers forget slower)."""
    base = 2.0 ** (-8.0 * jnp.arange(1, n_heads + 1, dtype=jnp.float32) / n_heads)
    return base * (1.0 - layer_index / max(n_layers - 1, 1) + 1e-5)


def linear_attention_step(state, q, k, v, slopes):
    """One position a row: state [N, H, d, d] float32, q/k/v [N, H, d] ->
    (out [N, H, d] in q's dtype, the new state)."""
    d = q.shape[-1]
    lam = jnp.exp(-slopes)[None, :, None, None]
    state = lam * state + jnp.einsum(
        "nhd,nhe->nhde", k, v, preferred_element_type=jnp.float32)
    out = jnp.einsum("nhd,nhde->nhe", q, state.astype(q.dtype),
                     preferred_element_type=jnp.float32) / math.sqrt(d)
    return out.astype(q.dtype), state


def _chunk(q, k, v, state, s, counts):
    """One chunk of one head: q/k/v [C, d], state [d, d] float32, ``s`` the
    head's slope, ``counts`` from :func:`_counts` -> (out [C, d] float32,
    the state after the chunk)."""
    C, d = q.shape
    dt = q.dtype
    count_to, count_row, exists, exists_row, count = counts
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    j = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    decay = jnp.where((j <= i) & exists_row,
                      jnp.exp(-s * (count_to - count_row)), 0.0)
    scores = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
    inner = jnp.dot((scores * decay).astype(dt), v,
                    preferred_element_type=jnp.float32)
    carried = jnp.dot(q, state.astype(dt), preferred_element_type=jnp.float32)
    out = (inner + jnp.exp(-s * count_to) * carried) / math.sqrt(d)
    keep = jnp.where(exists, jnp.exp(-s * (count - count_to)), 0.0)  # [C, 1]
    added = jax.lax.dot_general((k.astype(jnp.float32) * keep).astype(dt), v,
                                (((0,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    return out, jnp.exp(-s * count) * state + added


def _counts(n, c, C: int):
    """Of chunk ``c`` of a row with ``n`` existing positions: ``c_i`` as a
    column [C, 1] and as a row [1, C] (float32), whether position ``i``
    exists (column, row), and the chunk's whole count [1, 1]."""
    left = jnp.clip(n - c * C, 0, C)
    col = jax.lax.broadcasted_iota(jnp.int32, (C, 1), 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (1, C), 1)
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    # counts of positions, exponents' operands: float32 whatever q is
    whole = jnp.full((1, 1), left, jnp.float32)  # graftlint: disable=GL005
    return (f32(jnp.minimum(col + 1, left)), f32(jnp.minimum(row + 1, left)),
            col < left, row < left, whole)


def _scan_xla(q, k, v, slopes, n, C: int):
    B, P, H, d = q.shape

    def chunks(x):          # [B, P, H, d] -> [P/C, B, H, C, d]
        return x.reshape(B, P // C, C, H, d).transpose(1, 0, 3, 2, 4)

    def one(c, state, qc, kc, vc):
        def head(q1, k1, v1, s1, state1, n1):
            return _chunk(q1, k1, v1, state1, s1, _counts(n1, c, C))

        over_heads = jax.vmap(head, in_axes=(0, 0, 0, 0, 0, None))
        return jax.vmap(over_heads, in_axes=(0, 0, 0, None, 0, 0))(
            qc, kc, vc, slopes, state, n)

    def body(state, xs):
        c, qc, kc, vc = xs
        out, state = one(c, state, qc, kc, vc)
        return state, out.astype(q.dtype)

    # the state accumulates in float32 whatever the operands are
    state0 = jnp.zeros((B, H, d, d), jnp.float32)  # graftlint: disable=GL005
    state, out = jax.lax.scan(
        body, state0, (jnp.arange(P // C), chunks(q), chunks(k), chunks(v)))
    return out.transpose(1, 0, 3, 2, 4).reshape(B, P, H, d), state


def _kernel(n_ref, s_ref, q_ref, k_ref, v_ref, o_ref, st_ref, scr, *, C: int):
    b, h, c = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(c == 0)
    def _():
        scr[...] = jnp.zeros_like(scr)

    out, state = _chunk(q_ref[...], k_ref[...], v_ref[...], scr[...],
                        s_ref[h], _counts(n_ref[b], c, C))
    o_ref[...] = out.astype(o_ref.dtype)
    scr[...] = state

    @pl.when(c == pl.num_programs(2) - 1)
    def _():
        st_ref[...] = scr[...]


def _scan_pallas(q, k, v, slopes, n, C: int, interpret: bool):
    B, P, H, d = q.shape
    flat = lambda x: x.reshape(B, P, H * d)  # noqa: E731
    tile = pl.BlockSpec((None, C, d), lambda b, h, c, n_ref, s_ref: (b, c, h))
    out, state = pl.pallas_call(
        functools.partial(_kernel, C=C),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, H, P // C),
            in_specs=[tile, tile, tile],
            out_specs=[tile, pl.BlockSpec((None, None, d, d),
                                          lambda b, h, c, n_ref, s_ref: (b, h, 0, 0))],
            scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, P, H * d), q.dtype),
                   jax.ShapeDtypeStruct((B, H, d, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="linear_attn_prefill",
        interpret=interpret,
    )(n.astype(jnp.int32), slopes.astype(jnp.float32), flat(q), flat(k), flat(v))
    return out.reshape(B, P, H, d), state


def chunked_linear_attention(q, k, v, slopes, n, chunk: int = 256,
                             impl: str = "xla"):
    """The prefix: q/k/v [B, P, H, d], slopes [H], n [B] (existing positions
    a row) -> (out [B, P, H, d] in q's dtype, the state after the row's last
    existing position [B, H, d, d] float32). ``chunk`` need not divide P."""
    B, P, H, d = q.shape
    C = min(chunk, -(-P // 8) * 8)
    pad = (-P) % C
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    if impl == "pallas":
        out, state = _scan_pallas(q, k, v, slopes, n, C,
                                  interpret=jax.default_backend() != "tpu")
    else:
        out, state = _scan_xla(q, k, v, slopes, n, C)
    return out[:, :P], state
