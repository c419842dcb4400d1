"""Pallas TPU kernel: fused masked additive-attention context.

The per-step temporal attention computes

    s    = v . tanh(memory_proj + q[:, None, :])        # [B, M]
    s    = where(mask > 0, s, -1e9)
    ctx  = softmax_f32(s) @ memory                      # [B, E]

(models/attention.py — the CST paper's Bahdanau scoring). This kernel
streams the frame axis through VMEM in blocks with a flash-attention-style
online softmax: running (row max, denominator, weighted-sum accumulator)
scratch, one pass over M, and only [B, E] ever written back.

PERF STATUS: no benchmark cell reaches this kernel, so on today's code it is
not measured. A builder's run of round 4 (one TPU v5e, code older than PR 1;
BASELINE.md, "Pallas attention kernel") found the XLA composite tying or
beating it (within ±10%) at every M in {40..8192} x {f32, bf16}: the op is
bandwidth-bound on its inputs and XLA already fuses the [B, M, d_att] tanh
intermediate instead of materializing it (the original motivation for this
kernel). Opt-in (model.attention_impl="pallas"); there is no configuration
where it is recommended, and ROADMAP.md D2 lists it as the next deletion.

Numerics match the reference composite exactly in structure: masked slots
participate with score -1e9 (so a fully-masked row degrades to the same
uniform softmax over the M real slots), padding added for block alignment is
EXCLUDED from the softmax entirely, and all softmax statistics accumulate in
f32 regardless of the memory dtype.

The op is differentiable: a ``jax.custom_vjp`` whose backward re-runs the
plain XLA composite under ``jax.vjp`` (recompute-style — decode, the hot
path, never takes gradients; training pays one extra fused forward).

Off-TPU (CPU tests) the kernel runs in Pallas interpret mode automatically.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1.0e9


def _vma(*xs) -> frozenset:
    """Mesh axes any input is typed as varying over (shard_map's checker)."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _reference(q, v, memory, memory_proj, mask):
    """The XLA composite (attention.py math) — backward + parity oracle."""
    t = jnp.tanh(memory_proj + q[:, None, :])
    s = jnp.einsum("bmd,d->bm", t, v.astype(t.dtype))
    s = jnp.where(mask > 0, s, NEG).astype(jnp.float32)
    w = jax.nn.softmax(s, axis=-1).astype(memory.dtype)
    return jnp.einsum("bm,bme->be", w, memory)


def _kernel(q_ref, v_ref, mem_ref, proj_ref, mask_ref, o_ref,
            m_scr, d_scr, a_scr, *, m_true: int, block_m: int):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, -jnp.inf)
        d_scr[:] = jnp.zeros_like(d_scr)
        a_scr[:] = jnp.zeros_like(a_scr)

    q = q_ref[:]                                        # [Bb, d_att]
    t = jnp.tanh(proj_ref[:] + q[:, None, :]).astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)                    # [d_att]
    s = jnp.sum(t * v[None, None, :], axis=-1)          # [Bb, Mb] (VPU)
    s = jnp.where(mask_ref[:] > 0, s, NEG)
    # block-alignment padding is excluded from the softmax entirely;
    # merely-masked REAL slots stay in at -1e9 (reference semantics: a
    # fully-masked row yields the uniform softmax over its M real slots)
    col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) + j * block_m
    live = col < m_true
    s = jnp.where(live, s, -jnp.inf)

    m_prev = m_scr[:, 0]                                # [Bb]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1))
    # all-padding block (or first block): guard exp(-inf - -inf)
    alpha = jnp.where(
        m_prev == -jnp.inf, 0.0, jnp.exp(m_prev - m_cur)
    )
    p = jnp.where(live, jnp.exp(s - m_cur[:, None]), 0.0)  # [Bb, Mb]
    d_new = d_scr[:, 0] * alpha + jnp.sum(p, axis=-1)
    # batched [Bb,Mb] x [Bb,Mb,E] weighted sum on the VPU (Mosaic here has
    # no batched-dot lowering; the op is HBM-bandwidth-bound regardless)
    ctx = jnp.sum(
        p[:, :, None] * mem_ref[:].astype(jnp.float32), axis=1
    )                                                   # [Bb, E]
    a_new = a_scr[:] * alpha[:, None] + ctx

    m_scr[:, 0] = m_cur
    d_scr[:, 0] = d_new
    a_scr[:] = a_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        den = jnp.maximum(d_scr[:, 0], 1e-30)
        o_ref[:] = (a_scr[:] / den[:, None]).astype(o_ref.dtype)


def _pad_to(x, axis, mult, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _fused_forward(q, v, memory, memory_proj, mask,
                   block_b: int, block_m: int, interpret: bool):
    B, M, E = memory.shape
    d_att = q.shape[-1]
    qp = _pad_to(q, 0, block_b)
    memp = _pad_to(_pad_to(memory, 0, block_b), 1, block_m)
    projp = _pad_to(_pad_to(memory_proj, 0, block_b), 1, block_m)
    maskp = _pad_to(_pad_to(mask, 0, block_b), 1, block_m)
    Bp, Mp = maskp.shape

    # inside a shard_map with the varying-axis check on (the DP train step),
    # the output's vma must be declared: it varies over every axis any
    # input varies over
    out_shape = jax.ShapeDtypeStruct(
        (Bp, E), memory.dtype, vma=_vma(q, memory, memory_proj, mask)
    )

    grid = (Bp // block_b, Mp // block_m)
    out = pl.pallas_call(
        functools.partial(_kernel, m_true=M, block_m=block_m),
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_b, d_att), lambda i, j: (i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, d_att), lambda i, j: (0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, block_m, E), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, block_m, d_att), lambda i, j: (i, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((block_b, block_m), lambda i, j: (i, j),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (block_b, E), lambda i, j: (i, 0),
            memory_space=pltpu.VMEM,
        ),
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_b, 128), jnp.float32),   # running row max
            pltpu.VMEM((block_b, 128), jnp.float32),   # running denominator
            pltpu.VMEM((block_b, E), jnp.float32),     # weighted-sum acc
        ],
        interpret=interpret,
    )(qp, v.reshape(1, d_att), memp, projp, maskp)
    return out[:B]


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def fused_additive_attention(q, v, memory, memory_proj, mask,
                             block_b: int = 8, block_m: int = 128):
    """Fused masked additive-attention context -> [B, E].

    Args: ``q`` [B, d_att] (query_proj already applied), ``v`` [d_att] (the
    score vector), ``memory`` [B, M, E], ``memory_proj`` [B, M, d_att],
    ``mask`` [B, M]. Matches models/attention.py's composite bit-for-
    structure (see module docstring); gradients recompute via the composite.
    """
    interpret = jax.default_backend() != "tpu"
    if interpret and _vma(q, memory, memory_proj, mask):
        # Pallas INTERPRET mode can't execute under a varying-axis-checked
        # shard_map (the interpreter's loop constants are axis-invariant and
        # trip the vma check) — fall back to the composite there. Only the
        # CPU-test DP train step hits this; compiled Mosaic on TPU runs the
        # kernel in every context.
        return _reference(q, v, memory, memory_proj, mask)
    return _fused_forward(q, v, memory, memory_proj, mask,
                          block_b, block_m, interpret)


def _fwd(q, v, memory, memory_proj, mask, block_b, block_m):
    out = fused_additive_attention(q, v, memory, memory_proj, mask,
                                   block_b, block_m)
    return out, (q, v, memory, memory_proj, mask)


def _bwd(block_b, block_m, residuals, g):
    q, v, memory, memory_proj, mask = residuals
    _, vjp = jax.vjp(_reference, q, v, memory, memory_proj, mask)
    dq, dv, dmem, dproj, dmask = vjp(g)
    return dq, dv, dmem, dproj, dmask


fused_additive_attention.defvjp(_fwd, _bwd)
