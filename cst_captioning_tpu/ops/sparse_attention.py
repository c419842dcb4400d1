"""Block-sparse softmax attention whose key blocks each query selects
(InfLLM-v2 / MiniCPM4's ``minicpm4`` mixer), grouped-query.

A row's first ``n`` positions exist (the model compacts a clip's valid slots
to the front). Keys are mean-pooled over windows of ``kernel`` positions at
stride ``stride`` into **compressed keys** (:func:`compress_keys`; window
``c`` covers ``[c stride, c stride + kernel)`` and counts once it lies wholly
among the ``n``). For a query at position ``p`` (:func:`select_blocks`):

- every head's softmax over the compressed keys it sees (``c stride + kernel
  - 1 <= p``), scaled like the attention itself, float32, summed over the
  heads of its key/value group;
- a block of ``block`` keys scores the maximum over the compressed keys whose
  window meets it (``c`` in ``[b r - 1, b r + r - 1]``, ``r = block /
  stride``);
- the ``topk`` best blocks, and the first ``init_blocks``, are selected, one
  choice for all heads of the group; a query that sees fewer than
  ``dense_len`` keys selects every block.

The query then attends, causally, to the keys of its selected blocks and to
the last ``window`` positions (:func:`visible_keys` is that rule as a mask
over keys; :func:`attended_count` counts it without forming the mask).

The prefix (:func:`sparse_prefill`) never forms a ``[positions, positions]``
array: ``impl="xla"`` walks blocks of queries (scores ``[heads, queries a
block, keys]``), ``impl="pallas"`` is one flash kernel (``sparse_attn_prefill``
in a device trace: grid rows x groups x query tiles x key tiles, online
softmax, the block choice expanded to keys on the MXU, key tiles past the
causal edge skipped). At 16 k positions a selected block is a smaller read
than a page of a gather, and the chip's MXU walks the dense causal triangle
(2.2 TFLOP a clip and layer) in less time than it gathers 4096 keys a query:
the selection is applied as the kernel's mask, not as a gather. A decode step
(:func:`sparse_step`) is one query a lane over the clip's prefix keys, held
once a clip, and the lane's own caption keys.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1.0e30


class SparseSpec(NamedTuple):
    kernel: int = 32        # positions a compressed key pools
    stride: int = 16
    block: int = 64         # keys a selected block holds
    topk: int = 64
    window: int = 2048      # last positions always attended
    init_blocks: int = 1
    dense_len: int = 8192   # a query seeing fewer keys attends to all


def n_compressed(P: int, spec: SparseSpec) -> int:
    return max((P - spec.kernel) // spec.stride + 1, 0)


def n_blocks(P: int, spec: SparseSpec) -> int:
    return -(-P // spec.block)


def compress_keys(k, spec: SparseSpec):
    """k [B, P, G, d] -> [B, n_compressed, G, d] float32 window means."""
    B, P, G, d = k.shape
    C = n_compressed(P, spec)
    sums = jnp.cumsum(k.astype(jnp.float32), axis=1)
    sums = jnp.concatenate([jnp.zeros_like(sums[:, :1]), sums], axis=1)
    lo = jnp.arange(C) * spec.stride
    return (sums[:, lo + spec.kernel] - sums[:, lo]) / spec.kernel


def select_blocks(q, ck, n, q_pos, P: int, spec: SparseSpec):
    """q [B, Q, H, d], ck [B, C, G, d], n [B], q_pos [B, Q] (the queries'
    positions) -> (chosen [B, G, Q, nb] bool, dense [B, Q] bool: the queries
    that see fewer than ``dense_len`` keys and choose every block)."""
    B, Q, H, d = q.shape
    C, G = ck.shape[1], ck.shape[2]
    nb, r = n_blocks(P, spec), spec.block // spec.stride
    dense = q_pos + 1 < spec.dense_len
    if C == 0:
        return jnp.ones((B, G, Q, nb), bool), jnp.ones_like(dense)
    qg = q.reshape(B, Q, G, H // G, d)
    s = jnp.einsum("bqghd,bcgd->bghqc", qg, ck.astype(q.dtype),
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    end = jnp.arange(C) * spec.stride + spec.kernel       # a window's end
    seen = (end[None, None, :] <= q_pos[:, :, None] + 1) \
        & (end[None, None, :] <= n[:, None, None])        # [B, Q, C]
    s = jnp.where(seen[:, None, None], s, _NEG)
    p = jnp.where(seen[:, None, None], jax.nn.softmax(s, axis=-1), 0.0)
    score = p.sum(axis=2)                                 # [B, G, Q, C]
    score = jnp.where(seen[:, None], score, -1.0)
    # block b: the maximum over compressed keys b r - 1 .. b r + r - 1
    padded = jnp.pad(score, ((0, 0), (0, 0), (0, 0), (1, nb * r + r - C)),
                     constant_values=-1.0)
    by_block = jnp.stack([padded[..., i:i + nb * r:r] for i in range(r + 1)],
                         axis=-1).max(axis=-1)            # [B, G, Q, nb]
    k = min(spec.topk, nb)
    _, best = jax.lax.top_k(by_block, k)
    # a compare and a reduction, not a scatter
    chosen = (best[..., None] == jnp.arange(nb)).any(axis=-2)
    chosen = chosen | (jnp.arange(nb) < spec.init_blocks) \
        | dense[:, None, :, None]
    return chosen, dense


def visible_keys(chosen, q_pos, n, P: int, spec: SparseSpec):
    """The rule as a mask over the row's P keys: [B, G, Q, P] bool."""
    j = jnp.arange(P)
    by_key = jnp.repeat(chosen, spec.block, axis=-1)[..., :P]
    pos = q_pos[:, None, :, None]
    return (by_key | (j > pos - spec.window)) & (j <= pos) \
        & (j < n[:, None, None, None])


def attended_count(chosen, q_pos, n, spec: SparseSpec):
    """Keys among the row's first ``n`` that the rule lets each query attend
    to, without the mask: [B, G, Q] int32."""
    nb = chosen.shape[-1]
    lo = (jnp.arange(nb) * spec.block)[None, None, :]
    top = jnp.minimum(q_pos, n[:, None] - 1)[:, :, None]    # the last key seen
    seen = jnp.clip(top + 1 - lo, 0, spec.block)
    first = jnp.maximum(q_pos[:, :, None] - spec.window + 1, lo)
    near = jnp.clip(jnp.minimum(top, lo + spec.block - 1) - first + 1,
                    0, spec.block)
    return jnp.where(chosen, seen[:, None], near[:, None]).sum(-1).astype(jnp.int32)


def _queries_xla(q, k, v, ck, n, q_pos, spec: SparseSpec):
    """A block of queries against the whole prefix -> (out [B, Q, H, d],
    chosen, dense)."""
    B, Q, H, d = q.shape
    P, G = k.shape[1], k.shape[2]
    chosen, dense = select_blocks(q, ck, n, q_pos, P, spec)
    mask = visible_keys(chosen, q_pos, n, P, spec)            # [B, G, Q, P]
    qg = q.reshape(B, Q, G, H // G, d)
    s = jnp.einsum("bqghd,bpgd->bghqp", qg, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(mask[:, :, None], s, _NEG), axis=-1)
    out = jnp.einsum("bghqp,bpgd->bqghd", p.astype(q.dtype), v)
    return out.reshape(B, Q, H, d), chosen, dense


def _flash_kernel(n_ref, q_ref, k_ref, v_ref, c_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, tq: int, tk: int, heads: int, d: int,
                  block: int, window: int):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # a key tile wholly past the tile's last query holds nothing it may see
    @pl.when(ki * tk <= qi * tq + tq - 1)
    def _():
        nb = c_ref.shape[-1]
        key = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (nb, tk), 1)
        spread = (key // block == jax.lax.broadcasted_iota(
            jnp.int32, (nb, tk), 0)).astype(c_ref.dtype)
        by_key = jnp.dot(c_ref[...], spread,
                         preferred_element_type=jnp.float32)      # [tq, tk]
        i = qi * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        j = ki * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        ok = ((by_key > 0.5) | (j > i - window)) & (j <= i) & (j < n_ref[b])
        k, v = k_ref[...], v_ref[...]
        for h in range(heads):
            q = q_ref[:, h * d:(h + 1) * d]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(ok, s / math.sqrt(d), _NEG)
            m_old = m_scr[h]
            m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_old - m_new)
            l_scr[h] = alpha * l_scr[h] + p.sum(axis=-1, keepdims=True)
            acc_scr[h] = alpha * acc_scr[h] + jnp.dot(
                p.astype(v.dtype), v, preferred_element_type=jnp.float32)
            m_scr[h] = m_new

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        for h in range(heads):
            o_ref[:, h * d:(h + 1) * d] = (
                acc_scr[h] / jnp.maximum(l_scr[h], 1e-30)).astype(o_ref.dtype)


def _prefill_pallas(q, k, v, chosen, n, spec: SparseSpec, tq: int, tk: int,
                    interpret: bool):
    """q [B, P, H, d], k/v [B, P, G, d], chosen [B, G, P, nb] -> [B, P, H, d]."""
    B, P, H, d = q.shape
    G = k.shape[2]
    heads, nb = H // G, chosen.shape[-1]
    last_key = lambda qi: (qi * tq + tq - 1) // tk  # noqa: E731
    # a skipped key tile asks for the tile before it again: no new copy
    kv = pl.BlockSpec((None, tk, d),
                      lambda b, g, qi, ki, n_ref: (b, jnp.minimum(ki, last_key(qi)), g))
    rows = pl.BlockSpec((None, tq, heads * d), lambda b, g, qi, ki, n_ref: (b, qi, g))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, tq=tq, tk=tk, heads=heads, d=d,
                          block=spec.block, window=spec.window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G, P // tq, P // tk),
            in_specs=[rows, kv, kv,
                      pl.BlockSpec((None, None, tq, nb),
                                   lambda b, g, qi, ki, n_ref: (b, g, qi, 0))],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((heads, tq, 1), jnp.float32),
                            pltpu.VMEM((heads, tq, 1), jnp.float32),
                            pltpu.VMEM((heads, tq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, H * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="sparse_attn_prefill",
        interpret=interpret,
    )(n.astype(jnp.int32), q.reshape(B, P, H * d), k.reshape(B, P, G * d),
      v.reshape(B, P, G * d), chosen.astype(q.dtype))
    return out.reshape(B, P, H, d)


def sparse_prefill(q, k, v, ck, n, spec: SparseSpec, impl: str = "xla",
                   q_block: int = 512, tiles: tuple[int, int] = (256, 512)):
    """The prefix's queries over its own keys: q [B, P, H, d], k/v
    [B, P, G, d], ck from :func:`compress_keys`, n [B] -> (out [B, P, H, d],
    tally [B, 3] int32: keys the valid queries see, keys they attend to,
    queries under the dense length; each a key/value group)."""
    B, P, H, d = q.shape
    G = k.shape[2]
    Qb = min(q_block, P)
    pad = (-P) % Qb
    qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))) if pad else q
    blocks = qp.reshape(B, -1, Qb, H, d).transpose(1, 0, 2, 3, 4)
    starts = jnp.arange(blocks.shape[0]) * Qb

    def tally(chosen, dense, q_pos):
        live = q_pos < n[:, None]                                 # [B, Q]
        seen = jnp.minimum(q_pos + 1, n[:, None])
        took = attended_count(chosen, q_pos, n, spec)             # [B, G, Q]
        return jnp.stack([
            (jnp.where(live, seen, 0) * G).sum(-1),
            jnp.where(live[:, None], took, 0).sum((1, 2)),
            (live & dense).sum(-1) * G], axis=-1).astype(jnp.int32)

    if impl == "pallas":
        def choose(xs):
            qb, start = xs
            q_pos = jnp.broadcast_to(start + jnp.arange(Qb), (B, Qb))
            # a block of queries all under the dense length scores nothing
            chosen, dense = jax.lax.cond(
                start + Qb < spec.dense_len,
                lambda: (jnp.ones((B, G, Qb, n_blocks(P, spec)), bool),
                         jnp.ones((B, Qb), bool)),
                lambda: select_blocks(qb, ck, n, q_pos, P, spec))
            return chosen, tally(chosen, dense, q_pos)

        chosen, counts = jax.lax.map(choose, (blocks, starts))
        chosen = chosen.transpose(1, 2, 0, 3, 4).reshape(B, G, -1, chosen.shape[-1])
        tq, tk = (min(t, P) for t in tiles)
        edge = (-P) % math.lcm(tq, tk)
        grow = lambda x, axis: jnp.pad(  # noqa: E731
            x, [(0, edge if a == axis else 0) for a in range(x.ndim)])
        out = _prefill_pallas(
            grow(q, 1), grow(k, 1), grow(v, 1),
            grow(chosen[:, :, :P], 2), n, spec, tq, tk,
            interpret=jax.default_backend() != "tpu")[:, :P]
        return out, counts.sum(0)

    def one(xs):
        qb, start = xs
        q_pos = jnp.broadcast_to(start + jnp.arange(Qb), (B, Qb))
        out, chosen, dense = _queries_xla(qb, k, v, ck, n, q_pos, spec)
        return out, tally(chosen, dense, q_pos)

    out, counts = jax.lax.map(one, (blocks, starts))
    out = out.transpose(1, 0, 2, 3, 4).reshape(B, -1, H, d)[:, :P]
    return out, counts.sum(0)


def sparse_step(q, k_new, v_new, k, v, ck, n, t, k_own, v_own,
                spec: SparseSpec):
    """One query a row at position ``n + t``: q [N, H, d]; k_new/v_new
    [N, G, d] its own key and value; k/v [N, P, G, d] and ck the clip's
    prefix; k_own/v_own [N, T, G, d] the row's caption keys so far (position
    ``t`` is written here) -> (out [N, H, d], k_own, v_own, tally [N, 3])."""
    N, H, d = q.shape
    P, G, T = k.shape[1], k.shape[2], k_own.shape[1]
    rows = jnp.arange(N)
    k_own = k_own.at[rows, t].set(k_new.astype(k_own.dtype))
    v_own = v_own.at[rows, t].set(v_new.astype(v_own.dtype))
    q_pos = (n + t)[:, None]                                      # [N, 1]
    chosen, dense = select_blocks(q[:, None], ck, n, q_pos, P, spec)
    mask = visible_keys(chosen, q_pos, n, P, spec)[:, :, 0]       # [N, G, P]
    own = jnp.arange(T)[None, :] <= t[:, None]                    # [N, T]
    qg = q.reshape(N, G, H // G, d)
    s = jnp.concatenate([
        jnp.where(mask[:, :, None], jnp.einsum(
            "nghd,npgd->nghp", qg, k, preferred_element_type=jnp.float32), _NEG),
        jnp.where(own[:, None, None], jnp.einsum(
            "nghd,ntgd->nght", qg, k_own, preferred_element_type=jnp.float32),
            _NEG)], axis=-1) / math.sqrt(d)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("nghp,npgd->nghd", p[..., :P], v) \
        + jnp.einsum("nght,ntgd->nghd", p[..., P:], v_own)
    took = attended_count(chosen, q_pos, n, spec)[:, :, 0].sum(-1) + (t + 1) * G
    tally = jnp.stack([(n + t + 1) * G, took, dense[:, 0] * G], axis=-1)
    return out.reshape(N, H, d), k_own, v_own, tally.astype(jnp.int32)
