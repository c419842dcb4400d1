"""Causal grouped-query softmax attention with keys wider than values, in the
two forms one stack mixes (MiMo-V2's ``hybrid_layer_pattern``; models/
window_moe.py):

- **full**: query ``i`` sees the keys ``j <= i``;
- **window**: query ``i`` sees ``i - window < j <= i`` (``window`` keys with
  its own), and a learned **sink** a query head takes part in the softmax's
  denominator and adds nothing to the sum: ``p_ij = exp(s_ij) / (exp(b_h) +
  sum_j' exp(s_ij'))``.

``H`` query heads read ``G`` key/value heads (head ``h`` reads ``h // (H /
G)``); keys have ``dk`` numbers, values ``dv`` (192 and 128 as published);
``s_ij = q_i . k_j / sqrt(dk)`` in float32.

The prefix (:func:`window_prefill`, :func:`full_prefill`) never forms a
``[positions, positions]`` array. ``impl="xla"`` walks blocks of queries, a
window layer's against the slice of keys its band can reach, a full layer's
against all keys under a mask (the parity oracle, what runs off the TPU and
what a gradient can pass through). ``impl="pallas"`` is one flash kernel body
under three names (``window_attn_prefill`` / ``full_attn_prefill`` in a device
trace, and ``cca_attn_prefill`` for models/cca_moe.py's latent heads): grid
rows x key/value heads x query tiles x key tiles, online softmax, a key/value
head's ``H / G`` query heads in one block so that they share each
key tile's copy. A query tile walks only the key tiles its band (or the
diagonal) reaches: the grid's last axis is as long as the longest such walk,
a step past a tile's walk does nothing and asks for the tile before it again
(no new copy), and the mask is applied only on the tiles an edge cuts. A
query tile that begins at or past a row's ``n`` positions is skipped whole.
The sink joins the running denominator once a row, after the last key tile.
Keys and queries ride head-major (``[.., positions, dk]`` blocks: 192 is not
a multiple of the 128 lanes, so a head's keys cannot be a column block of a
flat ``[positions, heads x 192]`` array; as the last axis of the array they
are a legal block); the output is written flat, a head a 128-lane column.

A decode step (:func:`window_step`, :func:`full_step`) is one query a lane
over the keys its clip's lanes share (held once a clip: a full layer's whole
prefix, a window layer's last ``window`` prefix positions, :func:`tail_slice`)
and the lane's own caption keys, lanes leading: ``[lanes, clips, ...]``
against ``[clips, ...]``, so the shared keys are read from one copy.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1.0e30
# (query tile, key tile) of the two kernels: a block holds a key/value head's
# query heads (8 window, 16 full at the published widths), 2048 rows each way
WINDOW_TILES, FULL_TILES = (256, 128), (128, 512)
# the compressed-latent layers' (models/cca_moe.py): 4 query heads a block,
# 2048 rows as well
CCA_TILES = (512, 512)


def _with_sink(s, sink):
    """Softmax over the last axis of ``s [..., H-shaped, K]`` with the sink
    ``[...]`` broadcastable to ``s[..., 0]`` as one more column of the
    denominator (dropped from the result); a plain softmax without one."""
    if sink is None:
        return jax.nn.softmax(s, axis=-1)
    col = jnp.broadcast_to(sink.astype(jnp.float32)[..., None], s.shape[:-1] + (1,))
    return jax.nn.softmax(jnp.concatenate([s, col], axis=-1), axis=-1)[..., :-1]


def pair_counts(pos, window: int):
    """(window-layer pairs, full-layer pairs) a query at position ``pos``
    attends: ``min(pos + 1, window)`` and ``pos + 1``."""
    return jnp.minimum(pos + 1, window), pos + 1


# ---- the prefix ---------------------------------------------------------------


def _prefill_xla(q, k, v, sink, window: int | None, q_block: int = 512):
    """Blocks of queries: q [B, P, H, dk], k [B, P, G, dk], v [B, P, G, dv],
    sink [H] or None -> [B, P, H, dv]."""
    B, P, H, dk = q.shape
    G, dv = k.shape[2], v.shape[-1]
    r = H // G
    Qb = min(q_block, P)
    pad = (-P) % Qb
    reach = P + pad if window is None else Qb + window
    front = 0 if window is None else window
    q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(B, P + pad, G, r, dk)
    k, v = (jnp.pad(x, ((0, 0), (front, pad), (0, 0), (0, 0))) for x in (k, v))
    bias = None if sink is None else sink.reshape(G, r)[None, :, :, None]

    def one(start):
        qb = jax.lax.dynamic_slice_in_dim(q, start, Qb, 1)
        # a window layer's keys from position ``start - window`` on (the
        # arrays carry ``window`` rows of padding in front), a full layer's all
        at = start if window is not None else 0
        kb = jax.lax.dynamic_slice_in_dim(k, at, reach, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, at, reach, 1)
        i = (start + jnp.arange(Qb))[:, None]
        j = (at - front + jnp.arange(reach))[None, :]
        ok = (j >= 0) & (j <= i)
        if window is not None:
            ok &= j > i - window
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qb, kb,
                       preferred_element_type=jnp.float32) / math.sqrt(dk)
        p = _with_sink(jnp.where(ok, s, _NEG), bias).astype(q.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", p, vb)

    out = jax.lax.map(one, jnp.arange(0, P + pad, Qb))
    return out.transpose(1, 0, 2, 3, 4, 5).reshape(B, P + pad, H, dv)[:, :P]


def _walk(P: int, tq: int, tk: int, window: int | None):
    """The key tiles a query tile walks: -> (first(qi), last(qi), the longest
    walk): ``first`` and ``last`` work on Python ints and traced ints alike."""
    def first(qi):
        if window is None:
            return qi * 0
        return jnp.maximum(qi * tq - window + 1, 0) // tk

    def last(qi):
        return (qi * tq + tq - 1) // tk

    qi = np.arange(P // tq)
    lo = 0 * qi if window is None else np.maximum(qi * tq - window + 1, 0) // tk
    return first, last, int((last(qi) - lo).max()) + 1


def _flash_kernel(*refs, tq: int, tk: int, window: int | None, sink: bool):
    n_ref, q_ref, k_ref, v_ref = refs[:4]
    sink_ref = refs[4] if sink else None
    o_ref, m_scr, l_scr, acc_scr = refs[4 + sink:]
    b, qi, ki = pl.program_id(0), pl.program_id(2), pl.program_id(3)
    r, _, dk = q_ref.shape
    dv = v_ref.shape[-1]
    q0 = qi * tq
    lo = 0 if window is None else jnp.maximum(q0 - window + 1, 0) // tk
    kt = lo + ki
    k0 = kt * tk
    live = (q0 < n_ref[b]) & (k0 <= q0 + tq - 1)
    # an edge cuts the tile: the diagonal, or the band's far side
    cut = k0 + tk - 1 > q0
    if window is not None:
        cut |= k0 <= q0 + tq - 1 - window
    scale = 1.0 / math.sqrt(dk)

    @pl.when(ki == 0)
    def _():
        m_scr[...] = jnp.full_like(m_scr, _NEG)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def fold(masked: bool):
        s = jax.lax.dot_general(
            q_ref[...].reshape(r * tq, dk), k_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if masked:
            row = jax.lax.broadcasted_iota(jnp.int32, (r * tq, tk), 0)
            i = q0 + (row & (tq - 1) if tq & (tq - 1) == 0 else jax.lax.rem(row, tq))
            j = k0 + jax.lax.broadcasted_iota(jnp.int32, (r * tq, tk), 1)
            ok = j <= i
            if window is not None:
                ok &= j > i - window
            s = jnp.where(ok, s, _NEG)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, s.max(axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        if masked:
            p = jnp.where(ok, p, 0.0)
        alpha = jnp.exp(m_old - m_new)
        l_scr[...] = alpha * l_scr[...] + p.sum(axis=-1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jnp.dot(
            p.astype(v_ref.dtype), v_ref[...], preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(live & cut)
    def _():
        fold(True)

    @pl.when(live & jnp.logical_not(cut))
    def _():
        fold(False)

    @pl.when(ki == pl.num_programs(3) - 1)
    def _():
        m, l, acc = m_scr[...], l_scr[...], acc_scr[...]
        if sink:
            # the sink joins the denominator once a row, under a common maximum
            top = jnp.maximum(m, sink_ref[...])
            shrink = jnp.exp(m - top)
            l = l * shrink + jnp.exp(sink_ref[...] - top)
            acc = acc * shrink
        out = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        for h in range(r):      # a head a 128-lane column of the flat output
            o_ref[:, h * dv:(h + 1) * dv] = out[h * tq:(h + 1) * tq]


def _prefill_pallas(q, k, v, sink, n, window: int | None, tq: int, tk: int,
                    interpret: bool, name: str):
    """q [B, P, H, dk], k [B, P, G, dk], v [B, P, G, dv] with ``tq | P`` and
    ``tk | P`` -> [B, P, H, dv]."""
    B, P, H, dk = q.shape
    G, dv = k.shape[2], v.shape[-1]
    r = H // G
    first, last, steps = _walk(P, tq, tk, window)

    def keys(b, g, qi, ki, n_ref):
        # a step past the walk asks for the walk's last tile again: no new copy
        return b, g, jnp.minimum(first(qi) + ki, last(qi)), 0

    operands = [q.reshape(B, P, G, r, dk).transpose(0, 2, 3, 1, 4),
                k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)]
    in_specs = [
        pl.BlockSpec((None, None, r, tq, dk),
                     lambda b, g, qi, ki, n_ref: (b, g, 0, qi, 0)),
        pl.BlockSpec((None, None, tk, dk), keys),
        pl.BlockSpec((None, None, tk, dv), keys),
    ]
    if sink is not None:
        # a row of the block its head's sink: [G, r x tq, 1] float32
        operands.append(jnp.repeat(
            sink.astype(jnp.float32).reshape(G, r), tq, axis=1)[..., None])
        in_specs.append(pl.BlockSpec(
            (None, r * tq, 1), lambda b, g, qi, ki, n_ref: (g, 0, 0)))
    out = pl.pallas_call(
        functools.partial(_flash_kernel, tq=tq, tk=tk, window=window,
                          sink=sink is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, G, P // tq, steps),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, tq, r * dv), lambda b, g, qi, ki, n_ref: (b, qi, g)),
            scratch_shapes=[pltpu.VMEM((r * tq, 1), jnp.float32),
                            pltpu.VMEM((r * tq, 1), jnp.float32),
                            pltpu.VMEM((r * tq, dv), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, P, H * dv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        name=name,
        interpret=interpret,
    )(n.astype(jnp.int32), *operands)
    return out.reshape(B, P, H, dv)


def _prefill(q, k, v, sink, n, window, impl, tiles, name):
    if impl != "pallas":
        return _prefill_xla(q, k, v, sink, window)
    P = q.shape[1]
    tq, tk = tiles
    pad = (-P) % max(tq, tk)
    if max(tq, tk) % min(tq, tk):
        raise ValueError(f"one of the tiles {tiles} must divide the other")
    grow = lambda x: jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))  # noqa: E731
    out = _prefill_pallas(grow(q), grow(k), grow(v), sink, n, window, tq, tk,
                          interpret=jax.default_backend() != "tpu", name=name)
    return out[:, :P]


def window_prefill(q, k, v, sink, n, window: int, impl: str = "xla",
                   tiles: tuple[int, int] = WINDOW_TILES):
    """The prefix's queries over the last ``window`` keys each, the sink in
    the denominator: q [B, P, H, dk]; k [B, P, G, dk]; v [B, P, G, dv]; sink
    [H]; n [B]: the positions that exist -> out [B, P, H, dv]. A row's
    outputs from position ``n`` on are not defined (nothing reads them)."""
    return _prefill(q, k, v, sink, n, int(window), impl, tiles,
                    "window_attn_prefill")


def full_prefill(q, k, v, n, impl: str = "xla",
                 tiles: tuple[int, int] = FULL_TILES):
    """The prefix's queries over every key up to their own: shapes as
    :func:`window_prefill`, no sink."""
    return _prefill(q, k, v, None, n, None, impl, tiles, "full_attn_prefill")


def cca_prefill(q, k, v, n, impl: str = "xla",
                tiles: tuple[int, int] = CCA_TILES):
    """:func:`full_prefill` under a name of its own (``cca_attn_prefill`` in
    a device trace) and with tiles of its own: attention in a compressed
    latent (models/cca_moe.py), keys and values of one width, 4 query heads a
    key/value head."""
    return _prefill(q, k, v, None, n, None, impl, tiles, "cca_attn_prefill")


# ---- a decode step ------------------------------------------------------------


def tail_start(n, P: int, window: int):
    """Where each clip's tail slice begins, [B] int32: ``window`` positions
    back from ``n`` (a slice of ``min(window, P)`` inside the ``P``)."""
    return jnp.clip(n - window, 0, P - min(window, P)).astype(jnp.int32)


def tail_slice(k, start, window: int):
    """The prefix keys a caption behind it can still see in a window layer:
    k [B, P, G, d], start [B] (:func:`tail_start`) -> [B, G, W, d] head-major
    with ``W = min(window, P)``; entry ``s`` is position ``start + s``."""
    W = min(window, k.shape[1])
    rows = jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(a, s, W, 0))(k, start)
    return rows.transpose(0, 2, 1, 3)


def _write(own, new, t):
    """own [L, B, G, T, d] with new [L, B, G, d] written at index t [L, B]."""
    L, B, G, T, d = own.shape
    flat = own.reshape(L * B, G, T, d).at[jnp.arange(L * B), :, t.reshape(-1)].set(
        new.reshape(L * B, G, d).astype(own.dtype))
    return flat.reshape(own.shape)


def _step(q, k_new, v_new, k_shared, v_shared, ok_shared, t, k_own, v_own,
          ok_own, sink):
    """One query a lane and clip, q [L, B, H, dk] (its own key and value
    k_new, v_new [L, B, G, d] written into the lane's cache at ``t`` [L, B]
    first), over the clip's shared keys k_shared [B, G, S, dk] / v_shared
    under ok_shared [L, B, S] and the lane's own k_own [L, B, G, T, dk] /
    v_own under ok_own [L, B, T]: one softmax over both sets (and the sink
    [H] where there is one) -> (out [L, B, H, dv], k_own, v_own)."""
    L, B, H, dk = q.shape
    G = k_new.shape[2]
    r = H // G
    k_own, v_own = _write(k_own, k_new, t), _write(v_own, v_new, t)
    qg = q.reshape(L, B, G, r, dk)
    S = k_shared.shape[2]
    s = jnp.concatenate([
        jnp.where(ok_shared[:, :, None, None], jnp.einsum(
            "lbgrd,bgsd->lbgrs", qg, k_shared,
            preferred_element_type=jnp.float32), _NEG),
        jnp.where(ok_own[:, :, None, None], jnp.einsum(
            "lbgrd,lbgtd->lbgrt", qg, k_own,
            preferred_element_type=jnp.float32), _NEG)], axis=-1) / math.sqrt(dk)
    p = _with_sink(s, None if sink is None else sink.reshape(G, r)).astype(q.dtype)
    out = jnp.einsum("lbgrs,bgsd->lbgrd", p[..., :S], v_shared) \
        + jnp.einsum("lbgrt,lbgtd->lbgrd", p[..., S:], v_own)
    return out.reshape(L, B, H, -1), k_own, v_own


def full_step(q, k_new, v_new, keys, values, n, t, k_own, v_own):
    """A full layer's step: the clip's whole prefix ``keys`` / ``values``
    [B, G, P, d] (positions under ``n`` [B] exist) and the lane's caption
    keys up to token ``t`` [L, B]. Shapes as :func:`_step`."""
    ok_shared = jnp.arange(keys.shape[2])[None, None, :] < n[None, :, None]
    ok_shared = jnp.broadcast_to(ok_shared, t.shape + ok_shared.shape[-1:])
    ok_own = jnp.arange(k_own.shape[3])[None, None, :] <= t[..., None]
    return _step(q, k_new, v_new, keys, values, ok_shared, t, k_own, v_own,
                 ok_own, None)


def window_step(q, k_new, v_new, tail_k, tail_v, start, n, t, k_own, v_own,
                sink, window: int):
    """A window layer's step at position ``n + t``: the clip's tail slice
    ``tail_k`` / ``tail_v`` [B, G, W, d] from position ``start`` [B] on
    (:func:`tail_slice`) and the lane's caption keys, each under the band
    ``n + t - window < position <= n + t``; the sink [H] in the denominator."""
    pos = n[None, :] + t                                       # [L, B]
    at = start[None, :, None] + jnp.arange(tail_k.shape[2])    # [1, B, W]
    ok_shared = (at < n[None, :, None]) & (at > pos[..., None] - window)
    u = jnp.arange(k_own.shape[3])[None, None, :]
    ok_own = (u <= t[..., None]) & (u > t[..., None] - window)
    return _step(q, k_new, v_new, tail_k, tail_v, ok_shared, t, k_own, v_own,
                 ok_own, sink)
