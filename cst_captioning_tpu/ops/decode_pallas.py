"""Pallas TPU kernel: fused weight-stationary caption decode step.

One RL/eval decode step is, per lane ``g`` and batch row ``b`` (the exact
``DecoderCell.__call__`` math, models/decoder.py, dropout off — decode is
deterministic):

    q    = h_top @ Wq + bq                               # [B, A]
    s    = v . tanh(memory_proj + q[:, None, :])         # [B, M]
    ctx  = softmax_f32(where(mask, s, -1e9)) @ memory    # [B, E]
    x    = [word_emb(token), ctx]                        # [B, 2E]
    (c, h)_l = lstm_l(x) for each layer                  # [B, H]
    logits = h @ Wo + bo                                 # [B, V] f32

The XLA path lowers this to ~a dozen kernels per step, each re-reading its
operands from HBM; at round-5 dims the whole decode program ran at MFU
0.010 / bw_util 0.015 — latency-bound on dispatch, not on a resource. This
kernel runs the entire step as ONE ``pallas_call`` over a
``(batch-block, lane, vocab-block)`` grid in which every decoder weight has
a grid-invariant index map — Pallas fetches each weight block into VMEM
once and keeps it resident across the whole row grid (the weight-stationary
layout of TPU decode kernels, Ragged Paged Attention arXiv:2604.15464) —
and the memory bank block is fetched once per batch block and reused by all
1+K lanes. The output projection is blocked over the vocab axis
(``block_v``) so the full ``[H, V]`` matrix never has to fit VMEM; the
post-LSTM hidden is computed at the first vocab block and stashed in
scratch for the rest.

TWO kernels share that math:

- :func:`fused_decode_step` — the PR-4 per-step kernel: one launch per time
  step, weights resident across the row grid WITHIN the step. The embed
  gather and token selection stay outside (one XLA gather + argmax/
  categorical per step), so the XLA and Pallas impls share one RNG stream
  by construction. Still the kernel behind the greedy/sample loops.
- :func:`fused_decode_stride` — the multi-step stride kernel (see its
  section below): token selection and the next-token embedding lookup move
  IN-kernel, so weights stay resident across a whole stride of S time
  steps with ONE launch. RNG streams stay bit-identical because the Gumbel
  noise behind ``jax.random.categorical`` is precomputed outside from the
  ``rollout_step_keys`` streams and fed in as data. The fused RL decode
  (decoding/fused.py) drives this one.

Decode never takes gradients (the REINFORCE update teacher-forces through
its own path), so there is no VJP: differentiating the op raises.

Numerics: all compute in f32 regardless of the model dtype (scores, softmax,
gates); masked-but-real slots score -1e9 (a fully-masked row degrades to the
uniform softmax over its M real slots, reference semantics) while
block-alignment padding is EXCLUDED from the softmax entirely. Parity vs
the XLA step is pinned by the {f32, bf16} x {small, flagship-ish} sweep in
tests/test_ops_decode_pallas.py.

Off-TPU (CPU tests) the kernel runs in Pallas interpret mode automatically;
inside a varying-axis-checked shard_map in interpret mode it falls back to
the jnp composite.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID
from cst_captioning_tpu.models.decoder import LSTM_GATE_ORDER

NEG = -1.0e9


def _vma(*trees) -> frozenset:
    """Mesh axes any leaf is typed as varying over (the shard_map checker's
    view) — a kernel's outputs vary over every axis any input does."""
    out = frozenset()
    for x in jax.tree.leaves(trees):
        out |= jax.typeof(x).vma
    return out


def _num_layers(cell_params) -> int:
    n = sum(1 for k in cell_params if k.startswith("lstm"))
    if n == 0:
        raise ValueError("cell params carry no lstm<i> layers")
    return n


def _gate_weights(layer_params):
    """flax OptimizedLSTMCell per-gate Dense params -> (Wi [in, 4H],
    Wh [H, 4H], b [1, 4H]), concatenated in LSTM_GATE_ORDER — the same
    order the cell's own concatenated matmul splits on."""
    wi = jnp.concatenate(
        [layer_params[f"i{g}"]["kernel"] for g in LSTM_GATE_ORDER], axis=-1
    )
    wh = jnp.concatenate(
        [layer_params[f"h{g}"]["kernel"] for g in LSTM_GATE_ORDER], axis=-1
    )
    b = jnp.concatenate(
        [layer_params[f"h{g}"]["bias"] for g in LSTM_GATE_ORDER], axis=-1
    )
    return wi, wh, b[None, :]


def _lstm_math(x, c, h, wi, wh, b):
    """One OptimizedLSTMCell step in f32: gates split i|f|g|o."""
    gates = (
        jnp.dot(x, wi, preferred_element_type=jnp.float32)
        + jnp.dot(h, wh, preferred_element_type=jnp.float32)
        + b
    )
    i_, f_, g_, o_ = jnp.split(gates, 4, axis=-1)
    c_new = jax.nn.sigmoid(f_) * c + jax.nn.sigmoid(i_) * jnp.tanh(g_)
    h_new = jax.nn.sigmoid(o_) * jnp.tanh(c_new)
    return c_new, h_new


def _reference(cell_params, carry, token, memory, memory_proj, memory_mask,
               mem_lens=None, emb=None):
    """The decode step as a plain-jnp composite over the cell's param tree
    (f32 compute, like the kernel) — the interpret-mode shard_map fallback
    and the parity oracle's cross-check. ``mem_lens`` [B] excludes each
    row's memory columns >= its length from the softmax ENTIRELY (the
    per-row raggedness contract of the stride kernel below). ``emb``
    bypasses the embedding gather with pre-gathered rows — the
    vocab-sharded path (ops/decode_mp.py) gathers from its LOCAL embedding
    rows and psums, so a global-id gather here would be wrong there."""
    L = _num_layers(cell_params)
    if emb is None:
        emb = jnp.asarray(
            cell_params["word_embed"]["embedding"]
        )[token].astype(jnp.float32)
    else:
        emb = emb.astype(jnp.float32)
    wq = cell_params["attention"]["query_proj"]["kernel"].astype(jnp.float32)
    bq = cell_params["attention"]["query_proj"]["bias"].astype(jnp.float32)
    v = cell_params["attention"]["score"]["kernel"][:, 0].astype(jnp.float32)
    h_top = carry[-1][1].astype(jnp.float32)
    q = h_top @ wq + bq
    t = jnp.tanh(memory_proj.astype(jnp.float32)[None] + q[:, :, None, :])
    s = jnp.einsum("gbma,a->gbm", t, v)
    s = jnp.where(memory_mask[None] > 0, s, NEG)
    if mem_lens is not None:
        # rows keep >= 1 column so a fully-excluded row cannot NaN the
        # softmax (an unoccupied serving lane degrades to w=[1, 0, ..] over
        # zeroed memory — finite, and its frozen outputs never show it)
        lens = jnp.maximum(mem_lens.astype(jnp.int32), 1)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        s = jnp.where(col < lens[None, :, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("gbm,bme->gbe", w, memory.astype(jnp.float32))
    x = jnp.concatenate([emb, ctx], axis=-1)
    new_carry = []
    for layer in range(L):
        wi, wh, b = _gate_weights(cell_params[f"lstm{layer}"])
        c, h = carry[layer]
        c_new, h_new = _lstm_math(
            x, c.astype(jnp.float32), h.astype(jnp.float32),
            wi.astype(jnp.float32), wh.astype(jnp.float32),
            b.astype(jnp.float32),
        )
        new_carry.append((c_new.astype(c.dtype), h_new.astype(h.dtype)))
        x = h_new
    wo = cell_params["out_proj"]["kernel"].astype(jnp.float32)
    bo = cell_params["out_proj"]["bias"].astype(jnp.float32)
    logits = x @ wo + bo
    return tuple(new_carry), logits


def _kernel(*refs, num_layers: int, m_true: int):
    """Grid (batch-block i, lane g, vocab-block vb); weights grid-invariant.

    Ref layout (matching _fused_call's in_specs order):
      emb, [c_0, h_0, .., c_{L-1}, h_{L-1}], memory, proj, mask,
      wq, bq, v, [wi_0, wh_0, b_0, ..], wo, bo
      -> outputs: logits, [c_out_0, h_out_0, ..]; scratch: x_stash
    """
    L = num_layers
    it = iter(refs)
    emb_ref = next(it)
    carry_refs = [(next(it), next(it)) for _ in range(L)]
    mem_ref, proj_ref, mask_ref = next(it), next(it), next(it)
    wq_ref, bq_ref, v_ref = next(it), next(it), next(it)
    lstm_refs = [(next(it), next(it), next(it)) for _ in range(L)]
    wo_ref, bo_ref = next(it), next(it)
    logits_ref = next(it)
    carry_out_refs = [(next(it), next(it)) for _ in range(L)]
    x_scr = next(it)

    vb = pl.program_id(2)

    @pl.when(vb == 0)
    def _():
        h_top = carry_refs[L - 1][1][0].astype(jnp.float32)   # [Bb, H]
        q = (
            jnp.dot(h_top, wq_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
            + bq_ref[:].astype(jnp.float32)
        )                                                     # [Bb, A]
        t = jnp.tanh(proj_ref[:].astype(jnp.float32) + q[:, None, :])
        s = jnp.sum(t * v_ref[0].astype(jnp.float32)[None, None, :], axis=-1)
        s = jnp.where(mask_ref[:] > 0, s, NEG)                # [Bb, M]
        # alignment padding (cols >= m_true) leaves the softmax entirely;
        # merely-masked REAL slots stay in at -1e9 (reference semantics)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < m_true, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        w = p / jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.sum(
            w[:, :, None] * mem_ref[:].astype(jnp.float32), axis=1
        )                                                     # [Bb, E]
        x = jnp.concatenate(
            [emb_ref[0].astype(jnp.float32), ctx], axis=-1
        )
        for layer in range(L):
            c_ref, h_ref = carry_refs[layer]
            wi_ref, wh_ref, b_ref = lstm_refs[layer]
            c_new, h_new = _lstm_math(
                x,
                c_ref[0].astype(jnp.float32),
                h_ref[0].astype(jnp.float32),
                wi_ref[:].astype(jnp.float32),
                wh_ref[:].astype(jnp.float32),
                b_ref[:].astype(jnp.float32),
            )
            c_out, h_out = carry_out_refs[layer]
            c_out[0] = c_new.astype(c_out.dtype)
            h_out[0] = h_new.astype(h_out.dtype)
            x = h_new
        x_scr[:] = x

    logits_ref[0] = (
        jnp.dot(x_scr[:], wo_ref[:].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        + bo_ref[:].astype(jnp.float32)
    )


def _pad_to(x, axis, mult, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


# rows per (8, 128) f32 vreg tile: Mosaic takes a block's second-to-last
# dim only in whole tiles (or the whole array)
_SUBLANES = 8
_LANES = 128

# Mosaic's default scoped-VMEM budget (16 MiB on a v5e) is below what the
# weight-resident kernels hold at the paper's widths: the f32 LSTM gate
# matrices alone are 12 MiB at d=512, and the compiler asks ~30 MiB for the
# stride kernel at block_b=32, block_v=1024, V=9000. Half of the core's
# 128 MiB leaves room for the wider-than-paper models too; a kernel that
# outgrows it is refused at compile time, never silently spilled.
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 1024 * 1024)


def _batch_block(block_b: int, B: int, interpret: bool) -> int:
    """The batch block the grid runs: the requested one clamped to ``B``
    and, when Mosaic compiles it, rounded up to whole sublane tiles — the
    smallest block >= the request the chip accepts (batch rows pad up to
    it; padded rows are born finished). Interpret mode has no tiling and
    keeps the request exactly, so the CPU parity tests still pin the
    per-row ``block_b=1`` layout bit for bit."""
    block_b = max(1, min(block_b, B))
    if interpret:
        return block_b
    return -(-block_b // _SUBLANES) * _SUBLANES


def _fused_call(cell_params, carry, emb, memory, memory_proj, memory_mask,
                block_b: int, block_v: int, interpret: bool):
    L = _num_layers(cell_params)
    G, B, E = emb.shape
    M = memory.shape[1]
    Em = memory.shape[2]
    A = memory_proj.shape[2]
    H = carry[0][0].shape[-1]
    wo = cell_params["out_proj"]["kernel"]
    bo = cell_params["out_proj"]["bias"][None, :]
    V = wo.shape[-1]

    block_b = _batch_block(block_b, B, interpret)
    Bp = -(-B // block_b) * block_b
    block_v = min(block_v, -(-V // 128) * 128 if V > 128 else V)
    Vp = -(-V // block_v) * block_v
    Mp = -(-M // 128) * 128 if not interpret else M

    embp = _pad_to(emb, 1, block_b)
    carryp = [
        (_pad_to(c, 1, block_b), _pad_to(h, 1, block_b)) for c, h in carry
    ]
    memp = _pad_to(_pad_to(memory, 0, block_b), 1, Mp)
    projp = _pad_to(_pad_to(memory_proj, 0, block_b), 1, Mp)
    maskp = _pad_to(_pad_to(memory_mask, 0, block_b), 1, Mp)
    wop = _pad_to(wo, 1, block_v)
    bop = _pad_to(bo, 1, block_v)
    Mp = maskp.shape[1]

    att = cell_params["attention"]
    wq = att["query_proj"]["kernel"]
    bq = att["query_proj"]["bias"][None, :]
    vs = att["score"]["kernel"][:, 0][None, :]

    const = lambda i, g, vb: (0, 0)   # noqa: E731 — grid-invariant (resident)
    in_specs = [
        pl.BlockSpec((1, block_b, E), lambda i, g, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [embp]
    for c, h in carryp:
        for arr in (c, h):
            in_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )
            args.append(arr)
    in_specs += [
        pl.BlockSpec((block_b, Mp, Em), lambda i, g, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp, A), lambda i, g, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp), lambda i, g, vb: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((H, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
    ]
    args += [memp, projp, maskp, wq, bq, vs]
    for layer in range(L):
        wi, wh, b = _gate_weights(cell_params[f"lstm{layer}"])
        in_specs += [
            pl.BlockSpec(wi.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(wh.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, const, memory_space=pltpu.VMEM),
        ]
        args += [wi, wh, b]
    in_specs += [
        pl.BlockSpec((H, block_v), lambda i, g, vb: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_v), lambda i, g, vb: (0, vb),
                     memory_space=pltpu.VMEM),
    ]
    args += [wop, bop]

    # inside a varying-axis-checked shard_map the outputs' vma must be
    # declared
    sds = functools.partial(
        jax.ShapeDtypeStruct,
        vma=_vma(emb, memory, memory_proj, memory_mask, carry),
    )
    out_shape = [sds((G, Bp, Vp), jnp.float32)]
    out_specs = [
        pl.BlockSpec((1, block_b, block_v), lambda i, g, vb: (g, i, vb),
                     memory_space=pltpu.VMEM)
    ]
    for c, h in carry:
        for arr in (c, h):
            out_shape.append(sds((G, Bp, H), arr.dtype))
            out_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )

    grid = (Bp // block_b, G, Vp // block_v)
    outs = pl.pallas_call(
        functools.partial(_kernel, num_layers=L, m_true=M),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((block_b, H), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    logits = outs[0][:, :B, :V]
    flat = outs[1:]
    new_carry = tuple(
        (flat[2 * layer][:, :B], flat[2 * layer + 1][:, :B])
        for layer in range(L)
    )
    return new_carry, logits


def fused_decode_step(cell_params, carry, token, memory, memory_proj,
                      memory_mask, num_layers: int | None = None,
                      block_b: int = 32, block_v: int = 1024, emb=None):
    """Fused decode step -> (new_carry, logits [G, B, V] f32).

    Args: ``cell_params`` — the DecoderCell param subtree
    (``params["params"]["cell"]``); ``carry`` — tuple over layers of
    (c, h), leaves [G, B, H]; ``token`` [G, B] int32; ``memory`` [B, M, E] /
    ``memory_proj`` [B, M, A] / ``memory_mask`` [B, M] shared by all G
    lanes. ``emb`` [G, B, E] (optional) skips the internal embedding
    gather — the vocab-sharded caller (ops/decode_mp.py) supplies the
    psum-merged rows because its local table only holds a vocab slice.
    Inference-only: no VJP is defined (decode never takes gradients).
    """
    if num_layers is not None and num_layers != _num_layers(cell_params):
        raise ValueError(
            f"num_layers {num_layers} does not match the "
            f"{_num_layers(cell_params)} lstm layers in cell_params"
        )
    if emb is None:
        # the embed gather stays an XLA op (module docstring: keeping the
        # [V, E] table out of VMEM is what buys the other weights residency).
        # jnp.asarray: params may arrive as host numpy (a device_get'd
        # checkpoint), whose __getitem__ rejects traced token indices
        emb = jnp.asarray(cell_params["word_embed"]["embedding"])[token]
    interpret = jax.default_backend() != "tpu"
    # cell_params join the check: under the vocab-sharded shard_map
    # (ops/decode_mp.py) the activations are all invariant (emb arrives
    # psum-merged) but out_proj/word_embed vary over 'mp'
    if interpret and _vma(
        emb, memory, memory_proj, memory_mask, carry, cell_params
    ):
        # Pallas interpret mode can't run under a varying-axis-checked
        # shard_map — fall back to the composite (CPU tests only; compiled
        # Mosaic on TPU runs the kernel in every context)
        return _reference(
            cell_params, carry, token, memory, memory_proj, memory_mask,
            emb=emb,
        )
    return _fused_call(
        cell_params, carry, emb, memory, memory_proj, memory_mask,
        block_b, block_v, interpret,
    )


# ---- multi-step stride kernel: token selection moves INSIDE ------------------
#
# The per-step kernel above keeps weights resident for one time step; the
# stride kernel below keeps them resident across S steps by moving token
# selection and the next-token embedding lookup in-kernel, so the host
# dispatches ONE pallas_call per stride instead of one per step:
#
#   grid (batch-block i, lane g, step s, vocab-block vb) — s and vb are the
#   two inner (sequential) axes, so for each (i, g) the kernel walks S full
#   time steps while every decoder weight (grid-invariant index maps) and
#   the batch block's memory bank (invariant over g, s, vb) stay in VMEM.
#
# Selection semantics are EXACTLY the driving loop's (decoding/fused.py):
# lane 0 takes the first-index argmax of the untempered masked logits;
# lanes 1..K add precomputed Gumbel noise — jax.random.categorical's own
# Gumbel-max form, generated OUTSIDE from the [T, K] rollout_step_keys so
# the RNG streams stay bit-identical to the XLA path (the noise is data;
# only the argmax moved in-kernel). The blocked argmax keeps categorical's
# tie-break (lowest index wins: strictly-greater updates across vocab
# blocks, min-index within one). The chosen token's logprob comes from an
# online (max, sumexp) pair accumulated over the same vocab blocks.
#
# The next token's embedding never needs the [V, E] table resident: while
# vocab block vb streams through for the output projection, the embedding
# table block vb streams alongside it, and whenever a row's running argmax
# improves, that row one-hot-matmuls the candidate's embedding row out of
# the CURRENT table block into scratch (`pl.when(any(upd))` skips the
# matmul once the running max stops improving, which it quickly does). At
# the last vocab block the winner's embedding is already in scratch and
# becomes step s+1's input; finished rows feed PAD's embedding (stashed
# from block 0) — the exact frozen-token semantics of `step_outputs`.
#
# Finished-lane compaction hooks in through `n_active` (SMEM scalar): the
# driving loop packs batch columns that still have an unfinished lane into
# a dense prefix, and batch blocks entirely past the prefix skip attention,
# LSTM, projection and selection, writing only the frozen PAD/0 outputs and
# passing their carry through (a fully-finished column can never rejoin, so
# its stale carry is unobservable — the XLA path keeps stepping such rows,
# whose outputs are equally frozen). Per-lane raggedness inside an active
# block still steps (Ragged Paged Attention's per-page skipping is the
# natural next refinement); the compaction counters in the run report
# quantify exactly the column-level savings.

def _stride_kernel(*refs, num_layers: int, m_true: int, V: int, S: int,
                   temperature: float, min_len: int, block_v: int):
    L = num_layers
    it = iter(refs)
    t0_ref, nact_ref = next(it), next(it)
    emb0_ref, fin0_ref, lens_ref = next(it), next(it), next(it)
    carry_refs = [(next(it), next(it)) for _ in range(L)]
    mem_ref, proj_ref, mask_ref = next(it), next(it), next(it)
    wq_ref, bq_ref, v_ref = next(it), next(it), next(it)
    lstm_refs = [(next(it), next(it), next(it)) for _ in range(L)]
    wo_ref, bo_ref = next(it), next(it)
    embt_ref, noise_ref = next(it), next(it)
    tok_ref, lp_ref = next(it), next(it)
    carry_out_refs = [(next(it), next(it)) for _ in range(L)]
    x_scr, embc_scr, embn_scr, pade_scr = (
        next(it), next(it), next(it), next(it))
    bv_scr, bi_scr, sl_scr, lm_scr, ls_scr, fin_scr = (
        next(it), next(it), next(it), next(it), next(it), next(it))
    cs = [(next(it), next(it)) for _ in range(L)]

    i, g = pl.program_id(0), pl.program_id(1)
    s, vb = pl.program_id(2), pl.program_id(3)
    last_vb = vb == pl.num_programs(3) - 1
    bb = x_scr.shape[0]
    active = i * bb < nact_ref[0]

    @pl.when(active & (s == 0) & (vb == 0))
    def _():
        # per-(i, g) stride state lives in scratch; (re)seed it here
        embc_scr[:] = emb0_ref[0].astype(jnp.float32)
        fin_scr[:] = fin0_ref[0]
        for layer in range(L):
            cs[layer][0][:] = carry_refs[layer][0][0].astype(jnp.float32)
            cs[layer][1][:] = carry_refs[layer][1][0].astype(jnp.float32)
        # PAD's embedding row (PAD_ID == 0 lives in vocab block 0)
        pade_scr[:] = embt_ref[PAD_ID, :][None].astype(jnp.float32)

    # per-(lane-block, step) raggedness skip: once EVERY row of this lane's
    # batch block is finished, the remaining steps of the stride do no
    # attention/LSTM/projection/selection work — the finalize's frozen
    # branch (PAD/0 emission, PAD embedding feed) never reads the stale
    # selection scratch, and a fully-finished row's carry is unobservable
    # (compaction keeps such rows packed so whole blocks die together)
    live = active & jnp.any(fin_scr[:] == 0)

    @pl.when(live & (vb == 0))
    def _():
        # step s's attention + LSTM stack (the per-step kernel's math)
        h_top = cs[L - 1][1][:]
        q = (
            jnp.dot(h_top, wq_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
            + bq_ref[:].astype(jnp.float32)
        )
        t = jnp.tanh(proj_ref[:].astype(jnp.float32) + q[:, None, :])
        sc = jnp.sum(t * v_ref[0].astype(jnp.float32)[None, None, :], axis=-1)
        mask = mask_ref[:]
        if mask.ndim == 3:
            # the paged slab keeps slots on sublanes with the value
            # repeated across a lane tile (see _paged_stride_call); the
            # lane reduce moves them to lanes like the score reduce above
            mask = jnp.max(mask, axis=-1)
        sc = jnp.where(mask > 0, sc, NEG)
        # per-ROW raggedness: each row's memory columns past ITS length
        # leave the softmax entirely (serving's paged gathers are ragged
        # per request; exp underflow makes the exclusion bit-exact vs the
        # -1e9 masking a padded-slab layout would apply — see module
        # docstring). Uniform-length callers pass lens == m_true per row.
        mcol = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(mcol < lens_ref[:], sc, -jnp.inf)
        m = jnp.max(sc, axis=-1, keepdims=True)
        p = jnp.exp(sc - m)
        w = p / jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.sum(w[:, :, None] * mem_ref[:].astype(jnp.float32), axis=1)
        x = jnp.concatenate([embc_scr[:], ctx], axis=-1)
        for layer in range(L):
            wi_ref, wh_ref, b_ref = lstm_refs[layer]
            c_new, h_new = _lstm_math(
                x, cs[layer][0][:], cs[layer][1][:],
                wi_ref[:].astype(jnp.float32),
                wh_ref[:].astype(jnp.float32),
                b_ref[:].astype(jnp.float32),
            )
            cs[layer][0][:] = c_new
            cs[layer][1][:] = h_new
            x = h_new
        x_scr[:] = x
        # reset the per-step online selection / logsumexp state (-inf is
        # safe: every vocab block holds >= 1 real column, so the running
        # max is finite from the first block on — no inf-inf NaN path)
        bv_scr[:] = jnp.full_like(bv_scr[:], -jnp.inf)
        bi_scr[:] = jnp.zeros_like(bi_scr[:])
        sl_scr[:] = jnp.zeros_like(sl_scr[:])
        lm_scr[:] = jnp.full_like(lm_scr[:], -jnp.inf)
        ls_scr[:] = jnp.zeros_like(ls_scr[:])

    @pl.when(live)
    def _():
        logits = (
            jnp.dot(x_scr[:], wo_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
            + bo_ref[:].astype(jnp.float32)
        )                                                   # [bb, block_v]
        col = vb * block_v + jax.lax.broadcasted_iota(
            jnp.int32, logits.shape, 1
        )
        # forbid_special + apply_min_len, in-kernel
        logits = jnp.where((col == PAD_ID) | (col == BOS_ID), NEG, logits)
        if min_len > 0:
            t_glob = t0_ref[0] + s
            logits = jnp.where(
                (t_glob < min_len) & (col == EOS_ID), NEG, logits
            )
        lm = jnp.where(col < V, logits, -jnp.inf)  # padding cols: excluded
        # online logsumexp over the untempered masked logits (selected_logprob)
        bm = jnp.max(lm, axis=-1, keepdims=True)
        m_new = jnp.maximum(lm_scr[:], bm)
        ls_scr[:] = (
            ls_scr[:] * jnp.exp(lm_scr[:] - m_new)
            + jnp.sum(jnp.exp(lm - m_new), axis=-1, keepdims=True)
        )
        lm_scr[:] = m_new
        # selection value: untempered argmax on lane 0, Gumbel-max draw on
        # the sampled lanes (noise precomputed from rollout_step_keys)
        sel = jnp.where(
            g == 0, lm, lm / temperature + noise_ref[0, 0]
        )
        bm_s = jnp.max(sel, axis=-1, keepdims=True)
        cand = jnp.min(
            jnp.where(sel == bm_s, col, 2**30), axis=-1, keepdims=True
        )                       # first-max tie-break: lowest column id wins
        upd = bm_s > bv_scr[:]  # strict >: the earliest block keeps ties
        cand_lm = jnp.sum(
            jnp.where(col == cand, lm, 0.0), axis=-1, keepdims=True
        )
        bv_scr[:] = jnp.where(upd, bm_s, bv_scr[:])
        bi_scr[:] = jnp.where(upd, cand, bi_scr[:])
        sl_scr[:] = jnp.where(upd, cand_lm, sl_scr[:])

        @pl.when(jnp.any(upd))
        def _():
            # candidate embedding: one-hot row-select out of the CURRENT
            # table block (an MXU matmul, not a gather); skipped entirely
            # once no row's running argmax improves
            onehot = (col == cand).astype(jnp.float32)
            cand_emb = jnp.dot(
                onehot, embt_ref[:].astype(jnp.float32),
                preferred_element_type=jnp.float32,
            )
            embn_scr[:] = jnp.where(upd, cand_emb, embn_scr[:])

    @pl.when(active & last_vb)
    def _():
        # finalize step s: freeze finished rows (step_outputs semantics)
        fin = fin_scr[:] > 0
        tok = jnp.where(fin, jnp.int32(PAD_ID), bi_scr[:])
        lse = lm_scr[:] + jnp.log(ls_scr[:])
        lp = jnp.where(fin, 0.0, sl_scr[:] - lse)
        tok_ref[0, 0] = tok
        lp_ref[0, 0] = lp
        fin_scr[:] = jnp.logical_or(fin, tok == EOS_ID).astype(jnp.int32)
        embc_scr[:] = jnp.where(fin, pade_scr[:], embn_scr[:])

    @pl.when(active & (s == S - 1) & last_vb)
    def _():
        for layer in range(L):
            c_out, h_out = carry_out_refs[layer]
            c_out[0] = cs[layer][0][:].astype(c_out.dtype)
            h_out[0] = cs[layer][1][:].astype(h_out.dtype)

    # compacted-away blocks (every column fully finished): frozen outputs,
    # carry passthrough — no attention/LSTM/projection/selection work
    @pl.when(jnp.logical_not(active) & last_vb)
    def _():
        tok_ref[0, 0] = jnp.full((bb, 1), PAD_ID, jnp.int32)
        # frozen-row logprobs are f32 by the output contract
        lp_ref[0, 0] = jnp.zeros((bb, 1), jnp.float32)  # graftlint: disable=GL005

    @pl.when(jnp.logical_not(active) & (s == S - 1) & last_vb)
    def _():
        for layer in range(L):
            c_out, h_out = carry_out_refs[layer]
            c_out[0] = carry_refs[layer][0][0]
            h_out[0] = carry_refs[layer][1][0]


def _reference_stride(cell_params, carry, token, finished, memory,
                      memory_proj, memory_mask, noise, t0, *, steps: int,
                      temperature: float, min_len: int, mem_lens=None):
    """The stride kernel as a plain-jnp composite: S chained `_reference`
    steps with the driving loop's exact selection semantics (first-max
    argmax on lane 0, Gumbel-max on lanes 1..K from the provided noise,
    `selected_logprob` logprobs, `step_outputs` freezing) — the
    interpret-mode shard_map fallback and the parity oracle."""
    toks, lps = [], []
    for s in range(steps):
        carry, logits = _reference(
            cell_params, carry, token, memory, memory_proj, memory_mask,
            mem_lens=mem_lens,
        )
        neg = jnp.full_like(logits[..., :1], NEG)
        logits = (
            logits.at[..., PAD_ID].set(neg[..., 0])
            .at[..., BOS_ID].set(neg[..., 0])
        )
        if min_len > 0:
            blocked = logits.at[..., EOS_ID].set(NEG)
            logits = jnp.where(t0 + s < min_len, blocked, logits)
        g_nxt = jnp.argmax(logits[0], axis=-1)
        s_nxt = jnp.argmax(logits[1:] / temperature + noise[s], axis=-1)
        nxt = jnp.concatenate([g_nxt[None], s_nxt], axis=0).astype(jnp.int32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        lp = jnp.take_along_axis(logits, nxt[..., None], axis=-1)[..., 0] - lse
        nxt = jnp.where(finished, jnp.full_like(nxt, PAD_ID), nxt)
        lp = jnp.where(finished, jnp.zeros_like(lp), lp)
        finished = finished | (nxt == EOS_ID)
        toks.append(nxt)
        lps.append(lp)
        token = nxt
    return carry, jnp.stack(toks), jnp.stack(lps)


def _stride_call(cell_params, carry, emb0, finished, memory, memory_proj,
                 memory_mask, noise, t0, n_active, mem_lens, *, S: int,
                 temperature: float, min_len: int, block_b: int,
                 block_v: int, interpret: bool):
    L = _num_layers(cell_params)
    G, B, E = emb0.shape
    M = memory.shape[1]
    Em = memory.shape[2]
    A = memory_proj.shape[2]
    H = carry[0][0].shape[-1]
    wo = cell_params["out_proj"]["kernel"]
    bo = cell_params["out_proj"]["bias"][None, :]
    embt = jnp.asarray(cell_params["word_embed"]["embedding"])
    V = wo.shape[-1]

    block_b = _batch_block(block_b, B, interpret)
    Bp = -(-B // block_b) * block_b
    block_v = min(block_v, -(-V // 128) * 128 if V > 128 else V)
    Vp = -(-V // block_v) * block_v
    Mp = -(-M // 128) * 128 if not interpret else M

    emb0p = _pad_to(emb0, 1, block_b)
    # padded rows are born finished: their outputs freeze to PAD/0
    fin0p = _pad_to(finished.astype(jnp.int32), 1, block_b, value=1)[..., None]
    # per-row memory lengths (serving's ragged paged gathers); uniform M
    # when the caller passes none. Clamped to >= 1 so a zero-length row
    # (unoccupied serving lane, padding) keeps a finite softmax — its
    # frozen outputs never observe the uniform-over-one-zero-slot weights
    if mem_lens is None:
        mem_lens = jnp.full((B,), M, jnp.int32)
    lensp = _pad_to(
        jnp.clip(mem_lens.astype(jnp.int32), 1, M)[:, None], 0, block_b,
        value=1,
    )
    carryp = [
        (_pad_to(c, 1, block_b), _pad_to(h, 1, block_b)) for c, h in carry
    ]
    memp = _pad_to(_pad_to(memory, 0, block_b), 1, Mp)
    projp = _pad_to(_pad_to(memory_proj, 0, block_b), 1, Mp)
    maskp = _pad_to(_pad_to(memory_mask, 0, block_b), 1, Mp)
    wop = _pad_to(wo, 1, block_v)
    bop = _pad_to(bo, 1, block_v)
    embtp = _pad_to(embt, 0, block_v)
    noisep = _pad_to(_pad_to(noise, 2, block_b), 3, block_v)
    Mp = maskp.shape[1]

    att = cell_params["attention"]
    wq = att["query_proj"]["kernel"]
    bq = att["query_proj"]["bias"][None, :]
    vs = att["score"]["kernel"][:, 0][None, :]

    smem = pl.BlockSpec((1,), lambda i, g, s, vb: (0,),
                        memory_space=pltpu.SMEM)
    const = lambda i, g, s, vb: (0, 0)   # noqa: E731 — grid-invariant
    in_specs = [smem, smem]
    args = [
        jnp.asarray(t0, jnp.int32).reshape(1),
        jnp.asarray(n_active, jnp.int32).reshape(1),
    ]
    in_specs += [
        pl.BlockSpec((1, block_b, E), lambda i, g, s, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_b, 1), lambda i, g, s, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, 1), lambda i, g, s, vb: (i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [emb0p, fin0p, lensp]
    for c, h in carryp:
        for arr in (c, h):
            in_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, s, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )
            args.append(arr)
    in_specs += [
        pl.BlockSpec((block_b, Mp, Em), lambda i, g, s, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp, A), lambda i, g, s, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp), lambda i, g, s, vb: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((H, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
    ]
    args += [memp, projp, maskp, wq, bq, vs]
    for layer in range(L):
        wi, wh, b = _gate_weights(cell_params[f"lstm{layer}"])
        in_specs += [
            pl.BlockSpec(wi.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(wh.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, const, memory_space=pltpu.VMEM),
        ]
        args += [wi, wh, b]
    in_specs += [
        pl.BlockSpec((H, block_v), lambda i, g, s, vb: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_v), lambda i, g, s, vb: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_v, E), lambda i, g, s, vb: (vb, 0),
                     memory_space=pltpu.VMEM),
        # lane 0 draws no noise; its (unused) block aliases lane 1's so the
        # fetch is a repeat, not extra traffic
        pl.BlockSpec((1, 1, block_b, block_v),
                     lambda i, g, s, vb: (s, jnp.maximum(g - 1, 0), i, vb),
                     memory_space=pltpu.VMEM),
    ]
    args += [wop, bop, embtp, noisep]

    sds = functools.partial(
        jax.ShapeDtypeStruct,
        vma=_vma(
            emb0, memory, memory_proj, memory_mask, finished, noise, carry
        ),
    )
    out_shape = [sds((S, G, Bp, 1), jnp.int32),
                 sds((S, G, Bp, 1), jnp.float32)]
    out_specs = [
        pl.BlockSpec((1, 1, block_b, 1), lambda i, g, s, vb: (s, g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_b, 1), lambda i, g, s, vb: (s, g, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    for c, h in carry:
        for arr in (c, h):
            out_shape.append(sds((G, Bp, H), arr.dtype))
            out_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, s, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )

    scratch = [
        pltpu.VMEM((block_b, H), jnp.float32),    # x_stash
        pltpu.VMEM((block_b, E), jnp.float32),    # current-step embedding
        pltpu.VMEM((block_b, E), jnp.float32),    # candidate embedding
        pltpu.VMEM((1, E), jnp.float32),          # PAD embedding
        pltpu.VMEM((block_b, 1), jnp.float32),    # running best sel value
        pltpu.VMEM((block_b, 1), jnp.int32),      # running best token
        pltpu.VMEM((block_b, 1), jnp.float32),    # its untempered logit
        pltpu.VMEM((block_b, 1), jnp.float32),    # online lse max
        pltpu.VMEM((block_b, 1), jnp.float32),    # online lse sumexp
        pltpu.VMEM((block_b, 1), jnp.int32),      # finished
    ]
    for _ in range(L):
        scratch += [
            pltpu.VMEM((block_b, H), jnp.float32),
            pltpu.VMEM((block_b, H), jnp.float32),
        ]

    grid = (Bp // block_b, G, S, Vp // block_v)
    outs = pl.pallas_call(
        functools.partial(
            _stride_kernel, num_layers=L, m_true=M, V=V, S=S,
            temperature=temperature, min_len=min_len, block_v=block_v,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    tokens = outs[0][:, :, :B, 0]
    lps = outs[1][:, :, :B, 0]
    flat = outs[2:]
    new_carry = tuple(
        (flat[2 * layer][:, :B], flat[2 * layer + 1][:, :B])
        for layer in range(L)
    )
    return new_carry, tokens, lps


def fused_decode_stride(cell_params, carry, token, finished, memory,
                        memory_proj, memory_mask, noise, t0, n_active=None,
                        *, steps: int, temperature: float = 1.0,
                        min_len: int = 0, num_layers: int | None = None,
                        block_b: int = 32, block_v: int = 1024,
                        mem_lens=None):
    """S fused decode steps with in-kernel token selection.

    -> ``(new_carry, tokens [S, G, B] int32, logprobs [S, G, B] f32)``.

    Args beyond :func:`fused_decode_step`'s: ``finished`` [G, B] bool (rows
    already past EOS — they emit PAD/0 and feed PAD forward), ``noise``
    [S, K, B, V] f32 Gumbel noise for the sampled lanes (generated from the
    exact ``rollout_step_keys`` streams by the driving loop — see
    ``decoding.common.gumbel_step_noise``), ``t0`` the global index of the
    stride's first step (for ``min_len`` masking), and ``n_active`` the
    compaction prefix length in batch columns (None/B = no compaction —
    every block steps). ``mem_lens`` [B] int32 gives each row's OWN memory
    length: columns past it leave the attention softmax entirely — the
    per-row raggedness contract serving's paged gathers rely on (a request
    holding fewer pages attends over exactly its own slots; None = the
    uniform M every offline caller has). Lane 0 is the greedy lane:
    untempered first-index argmax, no noise consumed. Inference-only, like
    the per-step kernel.
    """
    if num_layers is not None and num_layers != _num_layers(cell_params):
        raise ValueError(
            f"num_layers {num_layers} does not match the "
            f"{_num_layers(cell_params)} lstm layers in cell_params"
        )
    G, B = token.shape
    if G < 2:
        raise ValueError(
            "fused_decode_stride needs the (1+K)-lane layout with K >= 1 "
            f"sampled lanes; got G={G}"
        )
    if noise.shape[:3] != (steps, G - 1, B):
        raise ValueError(
            f"noise shape {noise.shape} does not match "
            f"[steps={steps}, K={G - 1}, B={B}, V]"
        )
    if n_active is None:
        n_active = B
    interpret = jax.default_backend() != "tpu"
    if interpret and _vma(
        memory, memory_proj, memory_mask, finished, noise, carry
    ):
        # Pallas interpret mode can't run under a varying-axis-checked
        # shard_map — the composite carries it (CPU tests only)
        return _reference_stride(
            cell_params, carry, token, finished, memory, memory_proj,
            memory_mask, noise, t0, steps=steps, temperature=temperature,
            min_len=min_len, mem_lens=mem_lens,
        )
    emb0 = jnp.asarray(cell_params["word_embed"]["embedding"])[token]
    return _stride_call(
        cell_params, carry, emb0, finished, memory, memory_proj, memory_mask,
        noise, t0, n_active, mem_lens, S=steps, temperature=temperature,
        min_len=min_len, block_b=block_b, block_v=block_v,
        interpret=interpret,
    )


# ---- paged stride kernel: page-table reads move INSIDE -----------------------
#
# The serving engine keeps each request's encoder memory in fixed-size HBM
# pages (serving/pages.py, the Ragged Paged Attention layout of arXiv
# 2604.15464) — but until now every stride first GATHERED the active lanes'
# pages into the dense [B, W, E] bank the stride kernel consumes: a full
# copy of all live memory per stride (read pool + write bank + kernel
# re-reads bank = 3x the bank bytes), and a hard cap of one batch's dense
# footprint on how large the pool can usefully grow. The paged variant
# moves the page-table reads INSIDE the kernel:
#
#   the [B, max_pages] int32 page table rides as a SCALAR-PREFETCH operand
#   (pltpu.PrefetchScalarGridSpec) so its entries are available to the
#   kernel before the grid body runs; the three pools stay in HBM as
#   unblocked ANY-space refs; and at each batch block's FIRST grid visit
#   (g == 0, s == 0, vb == 0) the kernel DMAs each row's pages
#   ``pool.at[table[row, p]]`` into a per-block VMEM slab scratch
#   [block_b, W, *] (start-all-then-wait-all async copies). Scratch
#   persists across the (g, s, vb) inner axes, so the slab is fetched
#   ONCE per stride per batch block — exactly the residency the dense
#   path's memory BlockSpec gave — and every later grid step runs the
#   UNCHANGED dense stride kernel math against the slab refs.
#
# Bit-exactness vs the dense-gather path is by construction: the gather
# (`jnp.take` per pool) and the DMA fill produce the same bytes in the
# same [row, slot] layout (page 0 is the shared zero page either way), and
# `_stride_kernel` then executes the identical program on them. Per-row
# `mem_lens` raggedness composes unchanged: columns past a row's length
# leave the softmax via the same -inf masking, so a row holding fewer
# pages attends over exactly its own slots and the zero-page tail is
# mathematically (not just numerically) excluded. Finished-block skipping
# also composes: a compacted-away block (i past the n_active prefix)
# skips the DMA fill along with all other work.

def _paged_stride_kernel(*refs, num_layers: int, page_size: int,
                         table_width: int, pad_m: int, V: int, S: int,
                         temperature: float, min_len: int, block_v: int):
    L = num_layers
    # the dense kernel's operand counts: 5 leading + 2L carry + 3 bank +
    # 3 attention + 3L lstm + 4 trailing inputs; 2 + 2L outputs
    n_in = 15 + 5 * L
    n_out = 2 + 2 * L
    tbl_ref = refs[0]                       # scalar prefetch: [Bp, width]
    ins = refs[1:1 + n_in]
    outs = refs[1 + n_in:1 + n_in + n_out]
    slab_mem, slab_proj, slab_mask, dma_sem = refs[
        1 + n_in + n_out:5 + n_in + n_out]
    inner_scratch = refs[5 + n_in + n_out:]

    nact_ref = ins[1]
    # the pools sit at the dense kernel's mem/proj/mask positions, but as
    # unblocked HBM refs ([N+1, P, E] / [N+1, P, A] / [N+1, P])
    mem_hbm, proj_hbm, mask_hbm = ins[5 + 2 * L:8 + 2 * L]

    i = pl.program_id(0)
    first = (
        (pl.program_id(1) == 0) & (pl.program_id(2) == 0)
        & (pl.program_id(3) == 0)
    )
    bb = slab_mem.shape[0]
    active = i * bb < nact_ref[0]
    W = table_width * page_size

    @pl.when(active & first)
    def _():
        if pad_m:
            # TPU lane-alignment tail past the true W slots: zero it so the
            # (exactly-zero-weighted) context sum never reads uninitialized
            # VMEM — 0 * garbage is only 0 when the garbage is finite
            tail = pl.ds(W, pad_m)
            slab_mem[:, tail, :] = jnp.zeros(
                (bb, pad_m, slab_mem.shape[2]), slab_mem.dtype
            )
            slab_proj[:, tail, :] = jnp.zeros(
                (bb, pad_m, slab_proj.shape[2]), slab_proj.dtype
            )
            slab_mask[:, tail, :] = jnp.zeros(
                (bb, pad_m, slab_mask.shape[2]), slab_mask.dtype
            )
        copies = []
        for r in range(bb):
            for p in range(table_width):
                pg = tbl_ref[i * bb + r, p]
                dst = pl.ds(p * page_size, page_size)
                copies.append(pltpu.make_async_copy(
                    mem_hbm.at[pg], slab_mem.at[r, dst, :], dma_sem
                ))
                copies.append(pltpu.make_async_copy(
                    proj_hbm.at[pg], slab_proj.at[r, dst, :], dma_sem
                ))
                copies.append(pltpu.make_async_copy(
                    mask_hbm.at[pg], slab_mask.at[r, dst, :], dma_sem
                ))
        # start ALL page fetches before waiting on any: the DMA engine
        # overlaps them; program order only pins issue order
        for c in copies:
            c.start()
        for c in copies:
            c.wait()

    # the unchanged dense stride program, with the slab scratches standing
    # in for the dense bank's blocked refs — identical math on identical
    # bytes is the whole bit-exactness argument
    inner = (
        ins[:5 + 2 * L] + (slab_mem, slab_proj, slab_mask)
        + ins[8 + 2 * L:] + outs + inner_scratch
    )
    _stride_kernel(
        *inner, num_layers=L, m_true=W, V=V, S=S, temperature=temperature,
        min_len=min_len, block_v=block_v,
    )


def _gather_pages(mem_pool, proj_pool, mask_pool, table):
    """Dense [B, W, *] bank from pools + table — the XLA fallback and the
    parity oracle the paged kernel is pinned bit-exact against (page 0 is
    the shared zero page, so table padding gathers excluded slots)."""
    B, width = table.shape
    P = mem_pool.shape[1]
    flat = table.reshape(-1)
    mem = jnp.take(mem_pool, flat, axis=0).reshape(B, width * P, -1)
    proj = jnp.take(proj_pool, flat, axis=0).reshape(B, width * P, -1)
    mask = jnp.take(mask_pool, flat, axis=0).reshape(B, width * P)
    return mem, proj, mask


def _paged_stride_call(cell_params, carry, emb0, finished, mem_pool,
                       proj_pool, mask_pool, table, noise, t0, n_active,
                       mem_lens, *, S: int, temperature: float,
                       min_len: int, block_b: int, block_v: int,
                       interpret: bool):
    L = _num_layers(cell_params)
    G, B, E = emb0.shape
    P = mem_pool.shape[1]
    Em = mem_pool.shape[2]
    A = proj_pool.shape[2]
    width = table.shape[1]
    W = width * P
    H = carry[0][0].shape[-1]
    wo = cell_params["out_proj"]["kernel"]
    bo = cell_params["out_proj"]["bias"][None, :]
    embt = jnp.asarray(cell_params["word_embed"]["embedding"])
    V = wo.shape[-1]

    block_b = _batch_block(block_b, B, interpret)
    Bp = -(-B // block_b) * block_b
    block_v = min(block_v, -(-V // 128) * 128 if V > 128 else V)
    Vp = -(-V // block_v) * block_v
    Wp = -(-W // 128) * 128 if not interpret else W

    emb0p = _pad_to(emb0, 1, block_b)
    fin0p = _pad_to(finished.astype(jnp.int32), 1, block_b, value=1)[..., None]
    if mem_lens is None:
        mem_lens = jnp.full((B,), W, jnp.int32)
    lensp = _pad_to(
        jnp.clip(mem_lens.astype(jnp.int32), 1, W)[:, None], 0, block_b,
        value=1,
    )
    carryp = [
        (_pad_to(c, 1, block_b), _pad_to(h, 1, block_b)) for c, h in carry
    ]
    # padded table rows point every slot at the shared zero page
    tablep = _pad_to(table.astype(jnp.int32), 0, block_b)
    wop = _pad_to(wo, 1, block_v)
    bop = _pad_to(bo, 1, block_v)
    embtp = _pad_to(embt, 0, block_v)
    noisep = _pad_to(_pad_to(noise, 2, block_b), 3, block_v)

    att = cell_params["attention"]
    wq = att["query_proj"]["kernel"]
    bq = att["query_proj"]["bias"][None, :]
    vs = att["score"]["kernel"][:, 0][None, :]

    # index maps gain the trailing scalar-prefetch ref (PrefetchScalarGridSpec
    # passes it after the grid indices); none of them consults it — the
    # table is read in-kernel, not at block-selection time
    smem = pl.BlockSpec((1,), lambda i, g, s, vb, tbl: (0,),
                        memory_space=pltpu.SMEM)
    const = lambda i, g, s, vb, tbl: (0, 0)   # noqa: E731 — grid-invariant
    in_specs = [smem, smem]
    args = [
        jnp.asarray(t0, jnp.int32).reshape(1),
        jnp.asarray(n_active, jnp.int32).reshape(1),
    ]
    in_specs += [
        pl.BlockSpec((1, block_b, E), lambda i, g, s, vb, tbl: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_b, 1), lambda i, g, s, vb, tbl: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, 1), lambda i, g, s, vb, tbl: (i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [emb0p, fin0p, lensp]
    for c, h in carryp:
        for arr in (c, h):
            in_specs.append(
                pl.BlockSpec((1, block_b, H),
                             lambda i, g, s, vb, tbl: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )
            args.append(arr)
    in_specs += [
        # the pools stay whole in HBM; the kernel DMAs pages out by table id
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec(memory_space=pl.ANY),
        pl.BlockSpec((H, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
    ]
    # the mask pool rides lane-broadcast as [N+1, P, 128]: a page is then
    # P whole sublane rows of whole lane tiles in all three pools, the only
    # granule Mosaic's HBM->VMEM page DMA slices (a [1, P] lane slice of
    # the 2-D pool, or a 1-lane column, is refused off 128-alignment)
    mask_pool = jnp.broadcast_to(
        mask_pool[..., None], mask_pool.shape + (_LANES,)
    )
    args += [mem_pool, proj_pool, mask_pool, wq, bq, vs]
    for layer in range(L):
        wi, wh, b = _gate_weights(cell_params[f"lstm{layer}"])
        in_specs += [
            pl.BlockSpec(wi.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(wh.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, const, memory_space=pltpu.VMEM),
        ]
        args += [wi, wh, b]
    in_specs += [
        pl.BlockSpec((H, block_v), lambda i, g, s, vb, tbl: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_v), lambda i, g, s, vb, tbl: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_v, E), lambda i, g, s, vb, tbl: (vb, 0),
                     memory_space=pltpu.VMEM),
        # lane 0 draws no noise; its (unused) block aliases lane 1's so the
        # fetch is a repeat, not extra traffic
        pl.BlockSpec((1, 1, block_b, block_v),
                     lambda i, g, s, vb, tbl:
                     (s, jnp.maximum(g - 1, 0), i, vb),
                     memory_space=pltpu.VMEM),
    ]
    args += [wop, bop, embtp, noisep]

    sds = functools.partial(
        jax.ShapeDtypeStruct,
        vma=_vma(
            emb0, mem_pool, proj_pool, mask_pool, table, finished, noise,
            carry,
        ),
    )
    out_shape = [sds((S, G, Bp, 1), jnp.int32),
                 sds((S, G, Bp, 1), jnp.float32)]
    out_specs = [
        pl.BlockSpec((1, 1, block_b, 1),
                     lambda i, g, s, vb, tbl: (s, g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_b, 1),
                     lambda i, g, s, vb, tbl: (s, g, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    for c, h in carry:
        for arr in (c, h):
            out_shape.append(sds((G, Bp, H), arr.dtype))
            out_specs.append(
                pl.BlockSpec((1, block_b, H),
                             lambda i, g, s, vb, tbl: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )

    scratch = [
        # per-block page slabs, in the pools' OWN dtypes (the dense path
        # gathers without a cast, so the slab must hold the same bytes)
        pltpu.VMEM((block_b, Wp, Em), mem_pool.dtype),
        pltpu.VMEM((block_b, Wp, A), proj_pool.dtype),
        pltpu.VMEM((block_b, Wp, _LANES), mask_pool.dtype),
        pltpu.SemaphoreType.DMA,
        # the dense kernel's own scratch, unchanged
        pltpu.VMEM((block_b, H), jnp.float32),    # x_stash
        pltpu.VMEM((block_b, E), jnp.float32),    # current-step embedding
        pltpu.VMEM((block_b, E), jnp.float32),    # candidate embedding
        pltpu.VMEM((1, E), jnp.float32),          # PAD embedding
        pltpu.VMEM((block_b, 1), jnp.float32),    # running best sel value
        pltpu.VMEM((block_b, 1), jnp.int32),      # running best token
        pltpu.VMEM((block_b, 1), jnp.float32),    # its untempered logit
        pltpu.VMEM((block_b, 1), jnp.float32),    # online lse max
        pltpu.VMEM((block_b, 1), jnp.float32),    # online lse sumexp
        pltpu.VMEM((block_b, 1), jnp.int32),      # finished
    ]
    for _ in range(L):
        scratch += [
            pltpu.VMEM((block_b, H), jnp.float32),
            pltpu.VMEM((block_b, H), jnp.float32),
        ]

    grid = (Bp // block_b, G, S, Vp // block_v)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    outs = pl.pallas_call(
        functools.partial(
            _paged_stride_kernel, num_layers=L, page_size=P,
            table_width=width, pad_m=Wp - W, V=V, S=S,
            temperature=temperature, min_len=min_len, block_v=block_v,
        ),
        grid_spec=grid_spec,
        out_shape=out_shape,
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(tablep, *args)
    tokens = outs[0][:, :, :B, 0]
    lps = outs[1][:, :, :B, 0]
    flat = outs[2:]
    new_carry = tuple(
        (flat[2 * layer][:, :B], flat[2 * layer + 1][:, :B])
        for layer in range(L)
    )
    return new_carry, tokens, lps


def fused_decode_stride_paged(cell_params, carry, token, finished,
                              mem_pool, proj_pool, mask_pool, page_table,
                              noise, t0, n_active=None, *, steps: int,
                              temperature: float = 1.0, min_len: int = 0,
                              num_layers: int | None = None,
                              block_b: int = 32, block_v: int = 1024,
                              mem_lens=None):
    """:func:`fused_decode_stride` reading paged memory in-kernel.

    Same contract and returns, but the dense ``memory`` / ``memory_proj``
    / ``memory_mask`` bank is replaced by the page pools
    (``mem_pool [N+1, P, E]``, ``proj_pool [N+1, P, A]``,
    ``mask_pool [N+1, P]`` — row 0 is the shared zero page) plus a
    ``page_table [B, max_pages]`` int32 mapping each batch row to its pool
    rows (zero-page-padded past the row's own pages). The table rides as a
    scalar-prefetch operand and the kernel DMAs each batch block's pages
    from HBM into a VMEM slab once per stride — no dense [B, W, E] bank is
    ever materialized, so the pool may exceed one batch's dense footprint.
    Token- and logprob-bit-exact vs running :func:`fused_decode_stride`
    on the :func:`serving.pages.gather_bank` dense gather of the same
    pools (pinned in tests/test_ops_decode_pallas.py). ``mem_lens`` defaults to
    every row's full ``max_pages * P`` window; serving passes each row's
    true length. Inference-only, like the dense stride.
    """
    if num_layers is not None and num_layers != _num_layers(cell_params):
        raise ValueError(
            f"num_layers {num_layers} does not match the "
            f"{_num_layers(cell_params)} lstm layers in cell_params"
        )
    G, B = token.shape
    if G < 2:
        raise ValueError(
            "fused_decode_stride_paged needs the (1+K)-lane layout with "
            f"K >= 1 sampled lanes; got G={G}"
        )
    if noise.shape[:3] != (steps, G - 1, B):
        raise ValueError(
            f"noise shape {noise.shape} does not match "
            f"[steps={steps}, K={G - 1}, B={B}, V]"
        )
    if page_table.ndim != 2 or page_table.shape[0] != B:
        raise ValueError(
            f"page_table shape {page_table.shape} does not match "
            f"[B={B}, max_pages]"
        )
    if mem_pool.ndim != 3 or proj_pool.ndim != 3 or mask_pool.ndim != 2:
        raise ValueError(
            "pools must be [N+1, P, E] / [N+1, P, A] / [N+1, P]; got "
            f"{mem_pool.shape} / {proj_pool.shape} / {mask_pool.shape}"
        )
    if n_active is None:
        n_active = B
    interpret = jax.default_backend() != "tpu"
    if not interpret and mem_pool.shape[1] % _SUBLANES:
        # Mosaic: "Slice shape along dimension 1 must be aligned to tiling
        # (8)" — a page is DMA'd as whole sublane rows of its pool
        raise ValueError(
            f"fused_decode_stride_paged on TPU needs page_size % "
            f"{_SUBLANES} == 0 (pages are DMA'd as whole sublane tiles); "
            f"got page_size={mem_pool.shape[1]}"
        )
    if interpret and _vma(
        mem_pool, proj_pool, mask_pool, page_table, finished, noise, carry
    ):
        # Pallas interpret mode can't run under a varying-axis-checked
        # shard_map — gather the dense bank and run the composite (CPU
        # tests only; compiled Mosaic on TPU runs the kernel everywhere)
        memory, memory_proj, memory_mask = _gather_pages(
            mem_pool, proj_pool, mask_pool, page_table
        )
        return _reference_stride(
            cell_params, carry, token, finished, memory, memory_proj,
            memory_mask, noise, t0, steps=steps, temperature=temperature,
            min_len=min_len, mem_lens=mem_lens,
        )
    emb0 = jnp.asarray(cell_params["word_embed"]["embedding"])[token]
    return _paged_stride_call(
        cell_params, carry, emb0, finished, mem_pool, proj_pool, mask_pool,
        page_table, noise, t0, n_active, mem_lens, S=steps,
        temperature=temperature, min_len=min_len, block_b=block_b,
        block_v=block_v, interpret=interpret,
    )


# ---- beam step kernel: per-step top-k moves INSIDE ---------------------------
#
# The lane-batched beam search (decoding/beam.py, beam_impl="lanes") maps
# beams onto decode lanes, so its step is the per-step kernel above plus ONE
# extra reduction: the top-W candidate selection over (lane, vocab). The
# stride kernel's grid walks ALL steps of a lane before the next lane, which
# makes the beam's cross-lane hypothesis reorder impossible mid-stride —
# beams therefore ride a SINGLE-step launch (the reorder is a cross-lane
# gather the caller runs between launches, at the same seam where
# decoding/fused.py compacts finished columns), but the candidate selection
# itself moves in-kernel so the [G, B, V] logits never leave VMEM:
#
#   grid (batch-block i, lane g, vocab-block vb) — per vocab block the
#   kernel keeps (a) the stride kernel's online (max, sumexp) logsumexp and
#   (b) a running in-lane top-W over the raw masked logits, merged blockwise
#   (W max+mask passes — W is tiny). Raw-logit order equals logprob order
#   within a lane (the lse is one per-lane scalar subtracted uniformly), so
#   at the last vocab block the lane's W survivors become candidate totals
#   ``score + (logit - lse)`` — the exact `row_logprobs` association the XLA
#   beam scores with. Finished lanes contribute the closed-form PAD
#   continuation (score at PAD, score-1e9 at the next W-1 token ids), and a
#   cross-lane merge accumulated over g emits the global (total, flat) top-W
#   per row, ties broken toward the lower flat index like `lax.top_k`.
#
# Per-lane truncation to W is lossless: the global top-W takes at most W
# candidates from one lane, and in-lane ties keep the lowest column ids —
# the same order the flattened top_k would. (Known rounding edge: two
# DISTINCT raw logits whose totals round to equality at the f32 boundary
# candidate W could order differently than the reference's full sort; the
# parity suite has never observed it.) Requires W <= V so every lane can
# fill its candidate list.

def _beam_kernel(*refs, num_layers: int, m_true: int, V: int, W: int,
                 min_len: int, block_v: int):
    L = num_layers
    it = iter(refs)
    t_ref = next(it)
    emb_ref, fin_ref, sc_ref = next(it), next(it), next(it)
    carry_refs = [(next(it), next(it)) for _ in range(L)]
    mem_ref, proj_ref, mask_ref = next(it), next(it), next(it)
    wq_ref, bq_ref, v_ref = next(it), next(it), next(it)
    lstm_refs = [(next(it), next(it), next(it)) for _ in range(L)]
    wo_ref, bo_ref = next(it), next(it)
    tsc_ref, tfl_ref = next(it), next(it)
    carry_out_refs = [(next(it), next(it)) for _ in range(L)]
    x_scr, val_scr, idx_scr, lm_scr, ls_scr, cv_scr, cf_scr = (
        next(it), next(it), next(it), next(it), next(it), next(it), next(it))

    g, vb = pl.program_id(1), pl.program_id(2)
    G = pl.num_programs(1)
    last_vb = vb == pl.num_programs(2) - 1
    bb = x_scr.shape[0]

    @pl.when(vb == 0)
    def _():
        # lane g's attention + LSTM stack (the per-step kernel's math) and
        # carry write-out; then reset the per-lane selection state
        h_top = carry_refs[L - 1][1][0].astype(jnp.float32)
        q = (
            jnp.dot(h_top, wq_ref[:].astype(jnp.float32),
                    preferred_element_type=jnp.float32)
            + bq_ref[:].astype(jnp.float32)
        )
        t = jnp.tanh(proj_ref[:].astype(jnp.float32) + q[:, None, :])
        s = jnp.sum(t * v_ref[0].astype(jnp.float32)[None, None, :], axis=-1)
        s = jnp.where(mask_ref[:] > 0, s, NEG)
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(col < m_true, s, -jnp.inf)
        m = jnp.max(s, axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        w = p / jnp.sum(p, axis=-1, keepdims=True)
        ctx = jnp.sum(w[:, :, None] * mem_ref[:].astype(jnp.float32), axis=1)
        x = jnp.concatenate([emb_ref[0].astype(jnp.float32), ctx], axis=-1)
        for layer in range(L):
            c_ref, h_ref = carry_refs[layer]
            wi_ref, wh_ref, b_ref = lstm_refs[layer]
            c_new, h_new = _lstm_math(
                x, c_ref[0].astype(jnp.float32), h_ref[0].astype(jnp.float32),
                wi_ref[:].astype(jnp.float32), wh_ref[:].astype(jnp.float32),
                b_ref[:].astype(jnp.float32),
            )
            c_out, h_out = carry_out_refs[layer]
            c_out[0] = c_new.astype(c_out.dtype)
            h_out[0] = h_new.astype(h_out.dtype)
            x = h_new
        x_scr[:] = x
        # in-lane running top-W: -inf values under ids past any real column
        # (2**20 > any padded vocab id), so real candidates displace them
        val_scr[:] = jnp.full_like(val_scr[:], -jnp.inf)
        idx_scr[:] = 2**20 + jax.lax.broadcasted_iota(
            jnp.int32, idx_scr.shape, 1
        )
        lm_scr[:] = jnp.full_like(lm_scr[:], -jnp.inf)
        ls_scr[:] = jnp.zeros_like(ls_scr[:])

    logits = (
        jnp.dot(x_scr[:], wo_ref[:].astype(jnp.float32),
                preferred_element_type=jnp.float32)
        + bo_ref[:].astype(jnp.float32)
    )                                                   # [bb, block_v]
    col = vb * block_v + jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
    # forbid_special + apply_min_len, in-kernel (t is an SMEM scalar)
    logits = jnp.where((col == PAD_ID) | (col == BOS_ID), NEG, logits)
    if min_len > 0:
        logits = jnp.where(
            (t_ref[0] < min_len) & (col == EOS_ID), NEG, logits
        )
    lm = jnp.where(col < V, logits, -jnp.inf)  # padding cols: excluded
    # online logsumexp over the masked logits (the lane's row_logprobs lse)
    bm = jnp.max(lm, axis=-1, keepdims=True)
    m_new = jnp.maximum(lm_scr[:], bm)
    ls_scr[:] = (
        ls_scr[:] * jnp.exp(lm_scr[:] - m_new)
        + jnp.sum(jnp.exp(lm - m_new), axis=-1, keepdims=True)
    )
    lm_scr[:] = m_new
    # blocked in-lane top-W merge: union of this block's columns with the
    # running list (ids are globally unique — blocks cover disjoint column
    # ranges), W passes of (max, min-id-among-ties, mask-out) — `lax.top_k`
    # order: value descending, ties toward the lower id
    allv = jnp.concatenate([lm, val_scr[:]], axis=1)
    alli = jnp.concatenate([col, idx_scr[:]], axis=1)
    new_v, new_i = [], []
    for _ in range(W):
        mv = jnp.max(allv, axis=1, keepdims=True)
        pick = jnp.min(
            jnp.where(allv == mv, alli, 2**30), axis=1, keepdims=True
        )
        new_v.append(mv)
        new_i.append(pick)
        allv = jnp.where(alli == pick, -jnp.inf, allv)
    val_scr[:] = jnp.concatenate(new_v, axis=1)
    idx_scr[:] = jnp.concatenate(new_i, axis=1)

    @pl.when(last_vb)
    def _():
        # finalize lane g: W candidate (total, flat) pairs — live lanes
        # score their survivors in the row_logprobs association, finished
        # lanes emit the closed-form PAD continuation row's top-W
        lse = lm_scr[:] + jnp.log(ls_scr[:])            # [bb, 1]
        fin = fin_ref[0] > 0                            # [bb, 1]
        sc = sc_ref[0]                                  # [bb, 1]
        wio = jax.lax.broadcasted_iota(jnp.int32, (bb, W), 1)
        live_tot = sc + (val_scr[:] - lse)
        live_flat = g * V + idx_scr[:]
        fin_tot = sc + jnp.where(wio == 0, 0.0, NEG)
        fin_flat = g * V + wio                          # PAD, then ids 1..W-1
        tot = jnp.where(fin, fin_tot, live_tot)
        flat = jnp.where(fin, fin_flat, live_flat)

        @pl.when(g == 0)
        def _():
            cv_scr[:] = tot
            cf_scr[:] = flat

        @pl.when(g > 0)
        def _():
            # cross-lane merge: top-W of the 2W union, ties toward the
            # lower flat index (flats are unique across lanes)
            av = jnp.concatenate([cv_scr[:], tot], axis=1)
            ai = jnp.concatenate([cf_scr[:], flat], axis=1)
            mv_l, mi_l = [], []
            for _ in range(W):
                mv = jnp.max(av, axis=1, keepdims=True)
                pick = jnp.min(
                    jnp.where(av == mv, ai, 2**30), axis=1, keepdims=True
                )
                mv_l.append(mv)
                mi_l.append(pick)
                av = jnp.where(ai == pick, -jnp.inf, av)
            cv_scr[:] = jnp.concatenate(mv_l, axis=1)
            cf_scr[:] = jnp.concatenate(mi_l, axis=1)

        @pl.when(g == G - 1)
        def _():
            tsc_ref[:] = cv_scr[:]
            tfl_ref[:] = cf_scr[:]


def _reference_beam_topk(cell_params, carry, token, finished, scores,
                         memory, memory_proj, memory_mask, *, t,
                         min_len: int):
    """The beam step + candidate selection as a plain-jnp composite: one
    `_reference` step, `row_logprobs` scoring, PAD continuation for finished
    lanes, one `top_k` over the flattened W*V candidates — the interpret-
    mode shard_map fallback and the kernel's parity oracle."""
    new_carry, logits = _reference(
        cell_params, carry, token, memory, memory_proj, memory_mask
    )
    neg = jnp.full_like(logits[..., :1], NEG)
    logits = (
        logits.at[..., PAD_ID].set(neg[..., 0])
        .at[..., BOS_ID].set(neg[..., 0])
    )
    if min_len > 0:
        blocked = logits.at[..., EOS_ID].set(NEG)
        logits = jnp.where(t < min_len, blocked, logits)
    W, B = token.shape
    V = logits.shape[-1]
    logp = logits - jax.nn.logsumexp(logits, axis=-1, keepdims=True)
    logp = logp.transpose(1, 0, 2)                      # [B, W, V]
    pad_row = jnp.full((V,), NEG).at[PAD_ID].set(0.0)
    cont = jnp.where(finished.T[:, :, None], pad_row[None, None, :], logp)
    total = scores.T[:, :, None] + cont
    top_scores, flat = jax.lax.top_k(total.reshape(B, W * V), W)
    return new_carry, top_scores, flat.astype(jnp.int32)


def _beam_call(cell_params, carry, emb, finished, scores, memory,
               memory_proj, memory_mask, t, *, min_len: int, block_b: int,
               block_v: int, interpret: bool):
    L = _num_layers(cell_params)
    G, B, E = emb.shape
    M = memory.shape[1]
    Em = memory.shape[2]
    A = memory_proj.shape[2]
    H = carry[0][0].shape[-1]
    wo = cell_params["out_proj"]["kernel"]
    bo = cell_params["out_proj"]["bias"][None, :]
    V = wo.shape[-1]

    block_b = _batch_block(block_b, B, interpret)
    Bp = -(-B // block_b) * block_b
    block_v = min(block_v, -(-V // 128) * 128 if V > 128 else V)
    Vp = -(-V // block_v) * block_v
    Mp = -(-M // 128) * 128 if not interpret else M

    embp = _pad_to(emb, 1, block_b)
    # padded rows are born finished with score 0 — their candidate rows are
    # sliced off below, never merged into a real row's top-W (the merge is
    # per batch row)
    finp = _pad_to(finished.astype(jnp.int32), 1, block_b, value=1)[..., None]
    scp = _pad_to(scores.astype(jnp.float32), 1, block_b)[..., None]
    carryp = [
        (_pad_to(c, 1, block_b), _pad_to(h, 1, block_b)) for c, h in carry
    ]
    memp = _pad_to(_pad_to(memory, 0, block_b), 1, Mp)
    projp = _pad_to(_pad_to(memory_proj, 0, block_b), 1, Mp)
    maskp = _pad_to(_pad_to(memory_mask, 0, block_b), 1, Mp)
    wop = _pad_to(wo, 1, block_v)
    bop = _pad_to(bo, 1, block_v)
    Mp = maskp.shape[1]

    att = cell_params["attention"]
    wq = att["query_proj"]["kernel"]
    bq = att["query_proj"]["bias"][None, :]
    vs = att["score"]["kernel"][:, 0][None, :]

    smem = pl.BlockSpec((1,), lambda i, g, vb: (0,), memory_space=pltpu.SMEM)
    const = lambda i, g, vb: (0, 0)   # noqa: E731 — grid-invariant (resident)
    in_specs = [smem]
    args = [jnp.asarray(t, jnp.int32).reshape(1)]
    in_specs += [
        pl.BlockSpec((1, block_b, E), lambda i, g, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_b, 1), lambda i, g, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_b, 1), lambda i, g, vb: (g, i, 0),
                     memory_space=pltpu.VMEM),
    ]
    args += [embp, finp, scp]
    for c, h in carryp:
        for arr in (c, h):
            in_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )
            args.append(arr)
    in_specs += [
        pl.BlockSpec((block_b, Mp, Em), lambda i, g, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp, A), lambda i, g, vb: (i, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, Mp), lambda i, g, vb: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((H, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
        pl.BlockSpec((1, A), const, memory_space=pltpu.VMEM),
    ]
    args += [memp, projp, maskp, wq, bq, vs]
    for layer in range(L):
        wi, wh, b = _gate_weights(cell_params[f"lstm{layer}"])
        in_specs += [
            pl.BlockSpec(wi.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(wh.shape, const, memory_space=pltpu.VMEM),
            pl.BlockSpec(b.shape, const, memory_space=pltpu.VMEM),
        ]
        args += [wi, wh, b]
    in_specs += [
        pl.BlockSpec((H, block_v), lambda i, g, vb: (0, vb),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, block_v), lambda i, g, vb: (0, vb),
                     memory_space=pltpu.VMEM),
    ]
    args += [wop, bop]

    sds = functools.partial(
        jax.ShapeDtypeStruct,
        vma=_vma(
            emb, memory, memory_proj, memory_mask, finished, scores, carry
        ),
    )
    W = G
    out_shape = [sds((Bp, W), jnp.float32), sds((Bp, W), jnp.int32)]
    out_specs = [
        pl.BlockSpec((block_b, W), lambda i, g, vb: (i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((block_b, W), lambda i, g, vb: (i, 0),
                     memory_space=pltpu.VMEM),
    ]
    for c, h in carry:
        for arr in (c, h):
            out_shape.append(sds((G, Bp, H), arr.dtype))
            out_specs.append(
                pl.BlockSpec((1, block_b, H), lambda i, g, vb: (g, i, 0),
                             memory_space=pltpu.VMEM)
            )

    grid = (Bp // block_b, G, Vp // block_v)
    outs = pl.pallas_call(
        functools.partial(
            _beam_kernel, num_layers=L, m_true=M, V=V, W=W,
            min_len=min_len, block_v=block_v,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((block_b, H), jnp.float32),    # x_stash
            pltpu.VMEM((block_b, W), jnp.float32),    # in-lane top-W values
            pltpu.VMEM((block_b, W), jnp.int32),      # in-lane top-W col ids
            pltpu.VMEM((block_b, 1), jnp.float32),    # online lse max
            pltpu.VMEM((block_b, 1), jnp.float32),    # online lse sumexp
            pltpu.VMEM((block_b, W), jnp.float32),    # cross-lane totals
            pltpu.VMEM((block_b, W), jnp.int32),      # cross-lane flat ids
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
    )(*args)
    top_scores = outs[0][:B]
    top_flat = outs[1][:B]
    flat = outs[2:]
    new_carry = tuple(
        (flat[2 * layer][:, :B], flat[2 * layer + 1][:, :B])
        for layer in range(L)
    )
    return new_carry, top_scores, top_flat


def fused_beam_step(cell_params, carry, token, finished, scores, memory,
                    memory_proj, memory_mask, *, t, min_len: int = 0,
                    num_layers: int | None = None, block_b: int = 32,
                    block_v: int = 1024):
    """Fused beam step: decode + in-kernel top-W candidate selection.

    -> ``(new_carry, top_scores [B, W] f32, top_flat [B, W] int32)`` — the
    per-row global top-W over all (lane, token) candidates, ``flat = lane *
    V + token`` exactly like the XLA beam's flattened ``top_k``. The caller
    (decoding/beam.py) derives parent/token from ``flat`` and performs the
    hypothesis reorder between launches.

    Args beyond :func:`fused_decode_step`'s: ``finished`` [W, B] bool lanes
    already past EOS (they contribute the PAD continuation row),
    ``scores`` [W, B] f32 running hypothesis scores, ``t`` the global step
    index (traced; for ``min_len`` masking). Requires beam width <= vocab
    (section comment). Inference-only, like the other decode kernels.
    """
    if num_layers is not None and num_layers != _num_layers(cell_params):
        raise ValueError(
            f"num_layers {num_layers} does not match the "
            f"{_num_layers(cell_params)} lstm layers in cell_params"
        )
    W, B = token.shape
    V = cell_params["out_proj"]["kernel"].shape[-1]
    if W > V:
        raise ValueError(
            f"fused_beam_step needs beam width <= vocab to fill every "
            f"lane's candidate list; got W={W} > V={V}"
        )
    interpret = jax.default_backend() != "tpu"
    if interpret and _vma(
        memory, memory_proj, memory_mask, finished, scores, carry
    ):
        # Pallas interpret mode can't run under a varying-axis-checked
        # shard_map — the composite carries it (CPU tests only)
        return _reference_beam_topk(
            cell_params, carry, token, finished, scores, memory,
            memory_proj, memory_mask, t=t, min_len=min_len,
        )
    emb = jnp.asarray(cell_params["word_embed"]["embedding"])[token]
    return _beam_call(
        cell_params, carry, emb, finished, scores, memory, memory_proj,
        memory_mask, t, min_len=min_len, block_b=block_b, block_v=block_v,
        interpret=interpret,
    )
