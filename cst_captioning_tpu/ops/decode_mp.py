"""Sharded-vocab decode: the stride/beam kernels on an 'mp' model axis.

Flagship-XL vocabularies push the output projection ``[H, V]`` and the
embedding table ``[V, E]`` past one chip's weight budget. This module runs
the EXISTING decode kernels (ops/decode_pallas.py) unchanged on each model-
parallel shard over its vocab slice — Megatron-style column parallelism —
and recovers the replicated kernels' exact token stream with three small
cross-shard merges:

- **logsumexp** (and with it every logprob): online ``(m, s)`` merge —
  ``m = pmax(m_local)``, ``s = psum(s_local * exp(m_local - m))`` — tokens
  come out bit-exact, logprobs within a few f32 ulps of the one-shot
  reduction (reassociated sum);
- **argmax selection** (greedy + Gumbel lanes): each shard reports its
  local first-max (value, GLOBAL index); among the shards holding the
  ``pmax`` value ``pmin`` takes the lowest index, which — because the
  slices are disjoint and order-consistent — IS the global first-index
  argmax the replicated ``jnp.argmax`` computes. Bit-exact, not
  approximately;
- **top-W candidates** (beam): each shard top-Ws its ``[B, W * V_s]``
  slice, rebases local flat ids ``w * V_s + v`` into the replicated
  kernel's ``w * V + off + v`` namespace, and an explicit W-pass merge
  over the gathered ``mp * W`` candidates keeps ``lax.top_k``'s
  tie-to-lower-flat-id order exactly.

Every merge ends in a reduction (``psum`` / ``pmax`` / ``pmin``), never in
``all_gather``: a reduction's result is typed replicated over the axis, so
the programs keep ``check_vma`` on with ``out_specs`` ``P()``.

The next-token embedding under a row-sharded table is a masked LOCAL
gather (rows outside the shard contribute zeros) followed by one psum —
exact, since exactly one shard owns each token id. The recurrent cell
weights stay replicated on this path: the decode kernels consume them
whole, and their mp sharding (MP_PARAM_PARTITION_RULES) is a training-
side layout.

Everything here is built to run inside ``shard_map`` over the 'mp' axis of
a ``train.mesh.make_mesh(mp_devices=...)`` mesh; the ``mp_*`` wrappers
construct that program through parallel/compile.py. Parity with the
replicated kernels is pinned in tests/test_mp.py on the 8-device CPU mesh
(interpret mode — the per-shard kernel falls back to its composite there,
exactly like the unsharded path).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID
from cst_captioning_tpu.ops.decode_pallas import NEG, fused_decode_step
from cst_captioning_tpu.train.mesh import MP_PARAM_PARTITION_RULES, match_rule

# the decode path only shards the vocab dimension; these are the rule
# families (train/mesh.py) that carry it
VOCAB_FAMILIES = ("word_embed", "output_head_kernel", "output_head_bias")


def mp_cell_specs(cell_params, axis: str = "mp"):
    """PartitionSpecs for the DecoderCell subtree on the decode path:
    vocab-dimension families shard over ``axis``, everything else (the
    recurrent weights the kernels consume whole) replicates."""

    def spec(path, _leaf):
        name = "params/cell/" + "/".join(
            str(getattr(k, "key", k)) for k in path
        )
        family, s = match_rule(MP_PARAM_PARTITION_RULES, name)
        if family not in VOCAB_FAMILIES:
            return P()
        if axis == "mp":
            return s
        return P(*(axis if a == "mp" else a for a in s))

    return jax.tree_util.tree_map_with_path(spec, cell_params)


def _set_owned(x, global_id: int, off, value):
    """``x.at[..., global_id].set(value)`` when this shard's slice
    ``[off, off + V_s)`` owns the id; identity elsewhere."""
    vs = x.shape[-1]
    li = global_id - off
    owned = (li >= 0) & (li < vs)
    lic = jnp.clip(li, 0, vs - 1)
    return jnp.where(owned, x.at[..., lic].set(value), x)


def _psum_embed(table, token, off, axis: str):
    """Masked local gather + one psum: exact row-sharded embedding lookup
    (exactly one shard owns each id, the rest add zeros)."""
    vs = table.shape[0]
    li = token - off
    owned = (li >= 0) & (li < vs)
    lic = jnp.clip(li, 0, vs - 1)
    rows = table[lic]
    return jax.lax.psum(
        jnp.where(owned[..., None], rows, jnp.zeros_like(rows)), axis
    )


def _merge_argmax(vals, off, axis: str):
    """Global first-index argmax over vocab-sharded ``vals [..., V_s]``.

    Every shard offers its local first-max (value, GLOBAL index); the shards
    that hold the ``pmax`` value bid their index and ``pmin`` takes the
    lowest — the global first index, because the slices are ordered —
    matching the replicated ``jnp.argmax``. Both reductions are typed
    replicated over ``axis``, which ``out_specs`` ``P()`` requires under
    ``check_vma``."""
    lv = jnp.max(vals, axis=-1)
    li = jnp.argmax(vals, axis=-1).astype(jnp.int32) + off
    bid = jnp.where(lv == jax.lax.pmax(lv, axis), li, jnp.iinfo(jnp.int32).max)
    return jax.lax.pmin(bid, axis)


def _gather_replicated(x, axis: str, mp: int):
    """``all_gather(x [B, W], axis, axis=1)`` as ``[B, mp, W]`` typed
    replicated over ``axis`` (``all_gather`` itself is typed varying): every
    shard writes its rows into its own slot of zeros and one psum assembles
    them — exact, each slot has one non-zero addend."""
    mine = jnp.arange(mp)[None, :, None] == jax.lax.axis_index(axis)
    return jax.lax.psum(jnp.where(mine, x[:, None, :], 0), axis)


def _merge_lse(logits, axis: str):
    """Online (m, s) logsumexp across vocab shards: tokens downstream stay
    bit-exact; the value itself sits within a few f32 ulps of the one-shot
    ``jax.nn.logsumexp`` (the cross-shard sum reassociates)."""
    m_l = jnp.max(logits, axis=-1)
    m = jax.lax.pmax(m_l, axis)
    s = jax.lax.psum(
        jnp.sum(jnp.exp(logits - m[..., None]), axis=-1), axis
    )
    return m + jnp.log(s)


def _psum_select(logits, idx, off, axis: str):
    """The selected GLOBAL id's logit, summed from its owning shard."""
    vs = logits.shape[-1]
    li = idx - off
    owned = (li >= 0) & (li < vs)
    lic = jnp.clip(li, 0, vs - 1)
    val = jnp.take_along_axis(logits, lic[..., None], axis=-1)[..., 0]
    return jax.lax.psum(jnp.where(owned, val, 0.0), axis)


def _validate(cell_params, mesh, axis: str):
    if axis not in mesh.axis_names:
        raise ValueError(
            f"mesh axes {mesh.axis_names!r} have no {axis!r} axis — build "
            f"one with train.mesh.make_mesh(mp_devices=...)"
        )
    V = cell_params["out_proj"]["kernel"].shape[-1]
    mp = mesh.shape[axis]
    if V % mp:
        raise ValueError(
            f"vocab {V} does not divide over mp={mp} shards"
        )
    return V, mp


# ---- stride ------------------------------------------------------------------


def _stride_body(cell, carry, token, finished, memory, memory_proj,
                 memory_mask, noise, t0, *, steps: int, temperature: float,
                 min_len: int, axis: str):
    """Per-shard stride: S chained kernel steps with the driving loop's
    exact selection semantics (_reference_stride), selection merged across
    the vocab shards."""
    vs = cell["out_proj"]["kernel"].shape[-1]
    off = jax.lax.axis_index(axis) * vs
    table = jnp.asarray(cell["word_embed"]["embedding"])
    toks, lps = [], []
    for s in range(steps):
        emb = _psum_embed(table, token, off, axis)
        carry, logits = fused_decode_step(
            cell, carry, token, memory, memory_proj, memory_mask, emb=emb
        )
        logits = _set_owned(logits, PAD_ID, off, NEG)
        logits = _set_owned(logits, BOS_ID, off, NEG)
        if min_len > 0:
            blocked = _set_owned(logits, EOS_ID, off, NEG)
            logits = jnp.where(t0 + s < min_len, blocked, logits)
        g_nxt = _merge_argmax(logits[0], off, axis)
        s_nxt = _merge_argmax(
            logits[1:] / temperature + noise[s], off, axis
        )
        nxt = jnp.concatenate([g_nxt[None], s_nxt], axis=0).astype(jnp.int32)
        lse = _merge_lse(logits, axis)
        lp = _psum_select(logits, nxt, off, axis) - lse
        nxt = jnp.where(finished, jnp.full_like(nxt, PAD_ID), nxt)
        lp = jnp.where(finished, jnp.zeros_like(lp), lp)
        finished = finished | (nxt == EOS_ID)
        toks.append(nxt)
        lps.append(lp)
        token = nxt
    return carry, jnp.stack(toks), jnp.stack(lps)


def mp_decode_stride(cell_params, carry, token, finished, memory,
                     memory_proj, memory_mask, noise, t0, *, mesh,
                     steps: int, temperature: float = 1.0, min_len: int = 0,
                     axis: str = "mp"):
    """Vocab-sharded :func:`~cst_captioning_tpu.ops.decode_pallas.
    fused_decode_stride`: same signature semantics and the same
    ``(new_carry, tokens [S, G, B], logprobs [S, G, B])`` outputs, with the
    output head and embedding sharded over ``mesh``'s ``axis``.

    Tokens are bit-exact vs the replicated kernel; logprobs sit within a
    few f32 ulps (module docstring). ``noise`` [S, K, B, V] shards on its
    vocab dimension with the logits.
    """
    V, _ = _validate(cell_params, mesh, axis)
    if noise.shape[-1] != V:
        raise ValueError(
            f"noise vocab dim {noise.shape[-1]} != vocab {V}"
        )

    fn = _stride_program(
        mesh, jax.tree_util.tree_structure(cell_params), steps, temperature,
        min_len, axis,
    )
    return fn(cell_params, carry, token, finished, memory, memory_proj,
              memory_mask, noise, jnp.asarray(t0, jnp.int32))


@functools.lru_cache(maxsize=None)
def _stride_program(mesh, cell_treedef, steps: int, temperature: float,
                    min_len: int, axis: str):
    """One shard_map program per (mesh, cell structure, static knobs) —
    cached so repeated strides (the serving loop's shape) reuse the jit
    cache instead of rebuilding a fresh wrapper every call."""
    from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn

    def body(cell, carry, token, finished, memory, memory_proj, memory_mask,
             noise, t0):
        return _stride_body(
            cell, carry, token, finished, memory, memory_proj, memory_mask,
            noise, t0, steps=steps, temperature=temperature,
            min_len=min_len, axis=axis,
        )

    # mp_cell_specs only reads the tree's paths, so a structure-shaped
    # dummy yields the real specs
    dummy = jax.tree_util.tree_unflatten(
        cell_treedef, [0] * cell_treedef.num_leaves
    )
    return compile_fn(body, CompilePlan(
        mesh=mesh,
        in_specs=(mp_cell_specs(dummy, axis), P(), P(), P(), P(),
                  P(), P(), P(None, None, None, axis), P()),
        out_specs=(P(), P(), P()),
    ))


# ---- beam --------------------------------------------------------------------


def _merge_topw(pool_s, pool_f, W: int):
    """Top-W over per-shard candidate pools with ``lax.top_k``'s exact tie
    order: strictly-greater score wins, equal scores go to the lower GLOBAL
    flat id. Flat ids are globally unique per row, so eliminating the
    selected id by value is exact."""
    fmax = jnp.iinfo(jnp.int32).max
    alive = jnp.ones(pool_s.shape, bool)
    outs, outf = [], []
    for _ in range(W):
        s_eff = jnp.where(alive, pool_s, -jnp.inf)
        m = jnp.max(s_eff, axis=-1)
        is_m = alive & (s_eff == m[:, None])
        fsel = jnp.min(jnp.where(is_m, pool_f, fmax), axis=-1)
        outs.append(m)
        outf.append(fsel)
        alive = alive & (pool_f != fsel[:, None])
    return jnp.stack(outs, axis=1), jnp.stack(outf, axis=1).astype(jnp.int32)


def _beam_body(cell, carry, token, finished, scores, memory, memory_proj,
               memory_mask, t, *, min_len: int, axis: str, V: int, mp: int,
               W: int):
    """Per-shard beam step: the kernel over the local slice, a local top-W
    in the rebased global flat namespace, then the cross-shard merge."""
    vs = cell["out_proj"]["kernel"].shape[-1]
    off = jax.lax.axis_index(axis) * vs
    table = jnp.asarray(cell["word_embed"]["embedding"])
    B = token.shape[1]

    emb = _psum_embed(table, token, off, axis)
    carry, logits = fused_decode_step(
        cell, carry, token, memory, memory_proj, memory_mask, emb=emb
    )
    logits = _set_owned(logits, PAD_ID, off, NEG)
    logits = _set_owned(logits, BOS_ID, off, NEG)
    if min_len > 0:
        blocked = _set_owned(logits, EOS_ID, off, NEG)
        logits = jnp.where(t < min_len, blocked, logits)
    logp = logits - _merge_lse(logits, axis)[..., None]
    logp = logp.transpose(1, 0, 2)                       # [B, W, V_s]
    # the PAD continuation row, restricted to the columns this shard owns
    pad_row = _set_owned(jnp.full((vs,), NEG), PAD_ID, off, 0.0)
    cont = jnp.where(finished.T[:, :, None], pad_row[None, None, :], logp)
    total = scores.T[:, :, None] + cont
    ts, fl = jax.lax.top_k(total.reshape(B, W * vs), W)
    # local flat w * V_s + v -> the replicated kernel's w * V + off + v
    gf = (fl // vs) * V + off + (fl % vs)
    pool_s = _gather_replicated(ts, axis, mp).reshape(B, mp * W)
    pool_f = _gather_replicated(gf, axis, mp).reshape(B, mp * W)
    top_scores, top_flat = _merge_topw(pool_s, pool_f, W)
    return carry, top_scores, top_flat


def mp_beam_step(cell_params, carry, token, finished, scores, memory,
                 memory_proj, memory_mask, *, mesh, t, min_len: int = 0,
                 axis: str = "mp"):
    """Vocab-sharded :func:`~cst_captioning_tpu.ops.decode_pallas.
    fused_beam_step`: same ``(new_carry, top_scores [B, W], top_flat
    [B, W])`` outputs with ``flat = lane * V + token`` in the replicated
    kernel's namespace — candidate-for-candidate identical including
    ``top_k`` tie order (module docstring)."""
    V, mp = _validate(cell_params, mesh, axis)
    W, _B = token.shape
    if W > V // mp:
        raise ValueError(
            f"beam width {W} > per-shard vocab {V // mp}: every shard must "
            f"fill a full local top-{W} candidate list"
        )

    fn = _beam_program(
        mesh, jax.tree_util.tree_structure(cell_params), min_len, axis,
        V, mp, W,
    )
    return fn(cell_params, carry, token, finished, scores, memory,
              memory_proj, memory_mask, jnp.asarray(t, jnp.int32))


@functools.lru_cache(maxsize=None)
def _beam_program(mesh, cell_treedef, min_len: int, axis: str, V: int,
                  mp: int, W: int):
    """Cached shard_map beam program (see :func:`_stride_program`)."""
    from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn

    def body(cell, carry, token, finished, scores, memory, memory_proj,
             memory_mask, t):
        return _beam_body(
            cell, carry, token, finished, scores, memory, memory_proj,
            memory_mask, t, min_len=min_len, axis=axis, V=V, mp=mp, W=W,
        )

    dummy = jax.tree_util.tree_unflatten(
        cell_treedef, [0] * cell_treedef.num_leaves
    )
    return compile_fn(body, CompilePlan(
        mesh=mesh,
        in_specs=(mp_cell_specs(dummy, axis), P(), P(), P(), P(),
                  P(), P(), P(), P()),
        out_specs=(P(), P(), P()),
    ))
