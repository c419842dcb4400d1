"""EVA attention (Zheng et al., "Efficient Attention via Control Variates",
arXiv:2302.04542; EvaByte's ``attention_class: "eva"``): softmax attention
whose keys are kept at two granularities under one normaliser.

Positions are cut into **windows** of ``window`` and **chunks** of ``chunk``
(``chunk`` divides ``window``). A query at position ``i`` in window ``w = i //
window`` attends, causally, to

- the **exact** keys of its own window, ``j in [w window, i]``, and
- one **summary** for every chunk of every window before its own, ``c <
  (window / chunk) w``: a head's summary of chunk ``c`` is the softmax pooling
  of the chunk's keys and values under that head's learned ``phi`` (and the
  pooled key moved by its ``mu``; :func:`pool_chunks`),

one softmax over both sets, scores in float32. Only whole windows are ever
summarised, so every summarised chunk is whole.

The prefix (:func:`eva_prefill`) never forms a ``[positions, positions]``
array: ``impl="xla"`` walks blocks of queries against their own window's keys
and the summaries (the parity oracle, and what runs off the TPU);
``impl="pallas"`` is one flash kernel (``eva_attn_prefill`` in a device
trace: grid rows x heads x query tiles x key tiles, online softmax) whose key
walk for a query tile is the summary tiles of the windows before its own, one
tile a window, then its own window's exact tiles up to the diagonal. Summary
tiles of the own and later windows, exact tiles of other windows and exact
tiles past the diagonal are skipped, not masked: their grid steps do nothing
and ask for no new copy. A query tile that begins at or past a row's ``n``
positions is skipped whole.

A decode step (:func:`eva_step`) is one query a lane over four sets: the
clip's summaries and the clip's exact keys of the window its prefix ends in
(both held once a clip: :func:`window_slice`), the lane's own caption keys,
and the lane's own summaries: of the chunks that are not wholly the prefix's,
each made when its last key arrives and visible once the caption has left
that chunk's window. The state that holds a chunk thereby changes kind while
a caption runs: exact keys up to the crossing, a summary after it. A lane's
keys lie in a frame of whole chunks that begins at the chunk position ``n``
falls in (:func:`own_frame`: the prefix's last keys of that chunk, then the
caption's), so that a chunk of them is a static slice and no step gathers.
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


class EvaSpec(NamedTuple):
    window: int = 2048      # positions whose keys a query sees exactly
    chunk: int = 16         # positions one summary stands for

    @property
    def per_window(self) -> int:
        return self.window // self.chunk


def own_slots(max_len: int, spec: EvaSpec) -> int:
    """Chunks a caption of ``max_len`` positions can touch, wherever it
    starts: the summaries a lane holds."""
    return (max_len + spec.chunk - 2) // spec.chunk + 1


def key_counts(pos, spec: EvaSpec):
    """(exact keys, summaries) a query at position ``pos`` attends to."""
    return pos % spec.window + 1, spec.per_window * (pos // spec.window)


def pool_chunks(k, v, phi, mu, axis: int = -3):
    """k, v [..., d] whose axis ``axis`` runs over a chunk's positions; phi
    broadcastable to k and mu to the result (each head's own vector of d) ->
    (k~, v~) without that axis: a head's weights over a chunk's positions are
    the softmax of ``k . phi`` (float32); ``k~ = sum a k + mu``, ``v~ = sum a
    v``. The default layout is ``[..., chunk, H, d]`` with phi, mu ``[H, d]``."""
    kf, vf = k.astype(jnp.float32), v.astype(jnp.float32)
    axis = axis % k.ndim
    a = jax.nn.softmax((kf * phi.astype(jnp.float32)).sum(-1), axis=axis)[..., None]
    ks = (a * kf).sum(axis) + mu.astype(jnp.float32)
    return ks.astype(k.dtype), (a * vf).sum(axis).astype(v.dtype)


def chunk_summaries(k, v, phi, mu, spec: EvaSpec):
    """k, v [B, P, H, d] -> the summaries of the ``P // chunk`` whole chunks,
    each [B, P // chunk, H, d]."""
    B, P, H, d = k.shape
    C = P // spec.chunk
    cut = lambda x: x[:, :C * spec.chunk].reshape(B, C, spec.chunk, H, d)  # noqa: E731
    return pool_chunks(cut(k), cut(v), phi, mu)


def own_frame(k, n, max_len: int, spec: EvaSpec):
    """A lane's empty key (or value) cache behind ``n`` prefix positions:
    k [B, P, H, d] -> [B, H, own_slots x chunk, d], head-major. Index ``j``
    is position ``(n // chunk) chunk + j``: the frame begins at the chunk
    position ``n`` falls in, its first ``n % chunk`` entries are the prefix's
    last keys (the part of that chunk a caption does not bring), the rest is
    the caption's to fill, token ``t`` at ``n % chunk + t``."""
    B, P, H, d = k.shape
    at = (n // spec.chunk * spec.chunk)[:, None] + jnp.arange(spec.chunk)[None]
    edge = jnp.take_along_axis(k, jnp.clip(at, 0, P - 1)[:, :, None, None], axis=1)
    edge = jnp.where((at < n[:, None])[:, :, None, None], edge, 0)
    rest = jnp.zeros((B, (own_slots(max_len, spec) - 1) * spec.chunk, H, d), k.dtype)
    return jnp.concatenate([edge, rest], axis=1).transpose(0, 2, 1, 3)


def window_start(n, P: int, spec: EvaSpec):
    """Where each row's window slice begins, [B] int32: at the window
    position ``n`` lies in, moved back where a slice of ``min(window, P)``
    would run past the ``P`` positions (the positions before that window are
    then masked by their position)."""
    W = min(spec.window, P)
    return jnp.clip((n // spec.window) * spec.window, 0, P - W).astype(jnp.int32)


def window_slice(k, start, spec: EvaSpec):
    """The exact keys a caption behind the prefix can still see: k
    [B, P, H, d], start [B] (:func:`window_start`) -> [B, H, W, d] head-major
    with ``W = min(window, P)``."""
    W = min(spec.window, k.shape[1])
    rows = jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(a, s, W, 0))(k, start)
    return rows.transpose(0, 2, 1, 3)


# ---- the prefix ---------------------------------------------------------------


def _prefill_xla(q, k, v, ks, vs, spec: EvaSpec, q_block: int = 512):
    """Blocks of queries, each against its own window's keys and every
    summary it sees."""
    B, P, H, d = q.shape
    window, C = spec.window, ks.shape[1]
    Qb = q_block if window % q_block == 0 else window
    pad = (-P) % window
    grow = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))  # noqa: E731
    q, k, v = grow(q), grow(k), grow(v)

    def one(start):
        w = start // window
        cut = lambda x, at, size: jax.lax.dynamic_slice_in_dim(x, at, size, 1)  # noqa: E731
        qb = cut(q, start, Qb)
        kw, vw = cut(k, w * window, window), cut(v, w * window, window)
        i = start + jnp.arange(Qb)
        exact = (w * window + jnp.arange(window))[None, :] <= i[:, None]
        seen = jnp.arange(C) < spec.per_window * w
        s = jnp.concatenate([
            jnp.where(seen, jnp.einsum(
                "bqhd,bchd->bhqc", qb, ks, preferred_element_type=jnp.float32),
                _NEG),
            jnp.where(exact, jnp.einsum(
                "bqhd,bkhd->bhqk", qb, kw, preferred_element_type=jnp.float32),
                _NEG)], axis=-1) / math.sqrt(d)
        p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
        return jnp.einsum("bhqc,bchd->bqhd", p[..., :C], vs) \
            + jnp.einsum("bhqk,bkhd->bqhd", p[..., C:], vw)

    out = jax.lax.map(one, jnp.arange(0, P + pad, Qb))
    return out.transpose(1, 0, 2, 3, 4).reshape(B, -1, H, d)[:, :P]


def _flash_kernel(n_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref, m_scr,
                  l_scr, acc_scr, *, tq: int, tk: int, ns: int, window: int):
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    w, off = (qi * tq) // window, (qi * tq) % window
    live = qi * tq < n_ref[b]
    scale = 1.0 / math.sqrt(q_ref.shape[-1])

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(keys, values, ok=None):
        s = jax.lax.dot_general(q_ref[...], keys, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if ok is not None:
            s = jnp.where(ok, s, _NEG)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if ok is not None:
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(values.dtype), values, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    # summary tile ``ki`` is window ``ki``'s chunks: all seen by a query of a
    # later window, none by any other
    @pl.when(live & (ki < ns) & (ki < w))
    def _():
        fold(ks_ref[...], vs_ref[...])

    # exact tile ``ki - ns`` of the queries' own window, up to the diagonal
    @pl.when(live & (ki >= ns) & ((ki - ns) * tk <= off + tq - 1))
    def _():
        i = off + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 0)
        j = (ki - ns) * tk + jax.lax.broadcasted_iota(jnp.int32, (tq, tk), 1)
        fold(k_ref[...], v_ref[...], j <= i)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def _prefill_pallas(q, k, v, ks, vs, n, spec: EvaSpec, tq: int, tk: int,
                    interpret: bool):
    """q, k, v [B, P, H, d] with ``window | P``; ks, vs [B, P / chunk, H, d]
    -> [B, P, H, d]."""
    B, P, H, d = q.shape
    window, ts = spec.window, spec.per_window
    ns, ne = P // window, window // tk

    def own_window(qi):
        return (qi * tq) // window

    def summaries(b, h, qi, ki, n_ref):
        # a skipped step asks for the tile before it again: no new copy
        return b, jnp.clip(ki, 0, jnp.maximum(own_window(qi) - 1, 0)), h

    def exact(b, h, qi, ki, n_ref):
        last = ((qi * tq) % window + tq - 1) // tk
        return b, own_window(qi) * ne + jnp.clip(ki - ns, 0, last), h

    rows = pl.BlockSpec((None, tq, d), lambda b, h, qi, ki, n_ref: (b, qi, h))
    flat = lambda x: x.reshape(x.shape[0], x.shape[1], H * d)  # noqa: E731
    out = pl.pallas_call(
        functools.partial(_flash_kernel, tq=tq, tk=tk, ns=ns, window=window),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, H, P // tq, ns + ne),
            in_specs=[rows,
                      pl.BlockSpec((None, tk, d), exact),
                      pl.BlockSpec((None, tk, d), exact),
                      pl.BlockSpec((None, ts, d), summaries),
                      pl.BlockSpec((None, ts, d), summaries)],
            out_specs=rows,
            scratch_shapes=[pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, 1), jnp.float32),
                            pltpu.VMEM((tq, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, H * d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name="eva_attn_prefill",
        interpret=interpret,
    )(n.astype(jnp.int32), flat(q), flat(k), flat(v), flat(ks), flat(vs))
    return out.reshape(B, P, H, d)


def eva_prefill(q, k, v, ks, vs, n, spec: EvaSpec, impl: str = "xla",
                tiles: tuple[int, int] = (512, 512)):
    """The prefix's queries over its own keys: q, k, v [B, P, H, d]; ks, vs
    from :func:`chunk_summaries`; n [B]: the positions that exist -> out
    [B, P, H, d]. A row's outputs from position ``n`` on are not defined
    (nothing reads them)."""
    if impl != "pallas":
        return _prefill_xla(q, k, v, ks, vs, spec)
    P = q.shape[1]
    tq, tk = (min(t, spec.window) for t in tiles)
    if spec.window % tq or spec.window % tk or spec.window % spec.chunk:
        raise ValueError(f"tiles {(tq, tk)} and chunk {spec.chunk} must "
                         f"divide the window {spec.window}")
    pad = (-P) % spec.window
    C = (P + pad) // spec.chunk
    grow = lambda x, to: jnp.pad(  # noqa: E731
        x, ((0, 0), (0, to - x.shape[1]), (0, 0), (0, 0)))
    out = _prefill_pallas(
        grow(q, P + pad), grow(k, P + pad), grow(v, P + pad), grow(ks, C),
        grow(vs, C), n, spec, tq, tk,
        interpret=jax.default_backend() != "tpu")
    return out[:, :P]


# ---- a decode step ------------------------------------------------------------


def eva_step(q, k_new, v_new, ks, vs, kw, vw, start, n, t, k_own, v_own,
             ks_own, vs_own, phi, mu, spec: EvaSpec):
    """One query a row at position ``n + t``: q, k_new, v_new [N, H, d] (its
    own key and value); ks, vs [N, H, C, d] the clip's summaries and kw, vw
    [N, H, W, d], start [N] its window slice (:func:`window_slice`,
    :func:`window_start`), all
    head-major; k_own, v_own [N, H, S chunk, d] the row's own frame
    (:func:`own_frame`; index ``n % chunk + t`` is written here); ks_own,
    vs_own [N, H, S, d] the summaries the row has made of its frame's chunks
    (slot ``s`` is chunk ``n // chunk + s``; the chunk that position ``n + t``
    completes is written here) -> (out [N, H, d], k_own, v_own, ks_own,
    vs_own, tally [N, 3] int32: exact keys attended, summaries attended, 1
    where this position is the caption's first in a new window)."""
    N, H, d = q.shape
    C, W, J, S = ks.shape[2], kw.shape[2], k_own.shape[2], ks_own.shape[2]
    window, chunk = spec.window, spec.chunk
    rows = jnp.arange(N)
    pos, r = n + t, n % chunk
    k_own = k_own.at[rows, :, r + t].set(k_new.astype(k_own.dtype))
    v_own = v_own.at[rows, :, r + t].set(v_new.astype(v_own.dtype))
    w, c0 = pos // window, n // chunk
    col = lambda x: x[:, None]  # noqa: E731
    # which of each set the query sees
    held = jnp.arange(C)[None] < col(jnp.minimum(spec.per_window * w, c0))
    at = col(start) + jnp.arange(W)[None]
    near = (at >= col(w * window)) & (at < col(n))
    j = jnp.arange(J)[None]
    own = (j >= col(r)) & (j <= col(r + t)) & (col(c0 * chunk) + j >= col(w * window))
    made = col(c0) + jnp.arange(S)[None] < col(spec.per_window * w)
    sets = ((ks, vs, held), (kw, vw, near), (k_own, v_own, own),
            (ks_own, vs_own, made))
    s = jnp.concatenate(
        [jnp.where(ok[:, None], jnp.einsum(
            "nhd,nhkd->nhk", q, keys, preferred_element_type=jnp.float32), _NEG)
         for keys, _, ok in sets], axis=-1) / math.sqrt(d)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out, lo = 0.0, 0
    for _, values, ok in sets:
        hi = lo + ok.shape[-1]
        out = out + jnp.einsum("nhk,nhkd->nhd", p[..., lo:hi], values)
        lo = hi
    # the chunk this position completes becomes a summary of the row's own
    chunks = lambda x: x.reshape(N, H, S, chunk, d)  # noqa: E731
    k_sum, v_sum = pool_chunks(chunks(k_own), chunks(v_own),
                               phi[:, None, None], mu[:, None], axis=3)
    write = ((pos + 1) % chunk == 0)[:, None] \
        & (jnp.arange(S)[None] == col((r + t) // chunk))
    ks_own = jnp.where(write[:, None, :, None], k_sum, ks_own)
    vs_own = jnp.where(write[:, None, :, None], v_sum, vs_own)
    exact, summary = key_counts(pos, spec)
    tally = jnp.stack([exact, summary, (pos % window == 0) & (t > 0)], axis=-1)
    return out, k_own, v_own, ks_own, vs_own, tally.astype(jnp.int32)
