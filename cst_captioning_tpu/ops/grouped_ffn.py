"""The gated FFN ``(silu(x W_g) * x W_u) W_d`` as one grouped product: rows
sorted by group (an expert), each row tile a single group's, the tile's
group looked up in a small table (models/experts.py lays both out).

``xs [T x tile, h]`` holds ``T`` row tiles of which the first ``n_tiles``
exist; ``groups [T]`` names each tile's matrices among ``gate_w`` / ``up_w``
``[G, h, m]`` and ``down_w [G, m, h]`` (every layer's experts stacked: a tile
reads its own expert's where they lie, no copy of a layer's). A tile past
``n_tiles`` is not computed and comes back as zeros, so a group nobody chose
costs nothing and one everybody chose takes all its tiles.

``impl="pallas"`` is one kernel, ``held_experts_gmm`` in a device trace: grid
row tiles x column blocks of ``m``; a step takes ``x W_g`` and ``x W_u`` for
one column block in float32, rounds ``silu(.) * .`` to ``x``'s dtype once and
adds its product with the block's rows of ``W_d`` to a float32 accumulator,
which the tile's last step rounds to the output. The tile's group is
scalar-prefetched, so the matrices' blocks are copied while the tile before
computes; a tile that does not exist asks for the blocks of the last one that
does (no new copy). ``impl="xla"`` is the same tiles as one compiled map (the
parity oracle, what runs off the TPU and what a gradient can pass through).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_VMEM_LIMIT = 64 * 1024 * 1024
# what a step's blocks may take of it (two copies of what the pipeline moves)
_VMEM_BLOCKS = 40 * 1024 * 1024


def gated(x, gate, up, down):
    """The gated FFN ``(silu(x W_g) * x W_u) W_d`` in ``x``'s dtype."""
    dt = x.dtype
    return (jax.nn.silu(x @ gate.astype(dt)) * (x @ up.astype(dt))) @ down.astype(dt)


def impl() -> str:
    """The kernel on the TPU, the compiled map elsewhere."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def column_block(tile: int, h: int, m: int, itemsize: int) -> int:
    """Columns of ``m`` a kernel step takes: the widest of 512, 256, 128 that
    divides ``m`` and whose blocks fit (all of ``m`` where none divides it)."""
    def blocks(tn):
        rows = tile * h * (4 * itemsize + 4) + tile * tn * (8 + itemsize)
        return rows + 6 * h * tn * itemsize

    fits = [tn for tn in (512, 256, 128) if m % tn == 0]
    return next((tn for tn in fits if blocks(tn) <= _VMEM_BLOCKS),
                fits[-1] if fits else m)


def _kernel(groups_ref, n_ref, x_ref, gate_ref, up_ref, down_ref, o_ref, acc_ref):
    del groups_ref
    t, j, last = pl.program_id(0), pl.program_id(1), pl.num_programs(1) - 1

    @pl.when(t < n_ref[0])
    def _tile():
        @pl.when(j == 0)
        def _():
            acc_ref[...] = jnp.zeros_like(acc_ref)

        x = x_ref[...]
        g = jnp.dot(x, gate_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        u = jnp.dot(x, up_ref[...].astype(x.dtype),
                    preferred_element_type=jnp.float32)
        a = (g * jax.nn.sigmoid(g) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(a, down_ref[...].astype(x.dtype),
                                preferred_element_type=jnp.float32)

        @pl.when(j == last)
        def _():
            o_ref[...] = acc_ref[...].astype(o_ref.dtype)

    @pl.when((t >= n_ref[0]) & (j == last))
    def _none():
        o_ref[...] = jnp.zeros_like(o_ref)


def _pallas(xs, groups, n_tiles, gate_w, up_w, down_w, tile: int, interpret: bool):
    h, m = gate_w.shape[1:]
    T = xs.shape[0] // tile
    tn = column_block(tile, h, m, xs.dtype.itemsize)
    steps = m // tn

    def rows(t, j, groups_ref, n_ref):
        return jnp.maximum(jnp.minimum(t, n_ref[0] - 1), 0), 0

    def column(t, j, n_ref):
        # a tile that does not exist asks for the last step's blocks again
        return jnp.where(t < n_ref[0], j, steps - 1)

    def wide(t, j, groups_ref, n_ref):
        return groups_ref[t], 0, column(t, j, n_ref)

    def tall(t, j, groups_ref, n_ref):
        return groups_ref[t], column(t, j, n_ref), 0

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(T, steps),
            in_specs=[pl.BlockSpec((tile, h), rows),
                      pl.BlockSpec((None, h, tn), wide),
                      pl.BlockSpec((None, h, tn), wide),
                      pl.BlockSpec((None, tn, h), tall)],
            out_specs=pl.BlockSpec((tile, h), lambda t, j, g, n: (t, 0)),
            scratch_shapes=[pltpu.VMEM((tile, h), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct(xs.shape, xs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="held_experts_gmm",
        interpret=interpret,
    )(groups, n_tiles.reshape(1), xs, gate_w, up_w, down_w)


def _xla(xs, groups, n_tiles, gate_w, up_w, down_w, tile: int):
    h = xs.shape[-1]

    def one(args):
        t, rows = args
        g = groups[t]
        return jax.lax.cond(
            t < n_tiles,
            lambda: gated(rows, gate_w[g], up_w[g], down_w[g]),
            lambda: jnp.zeros_like(rows))

    tiles = xs.reshape(-1, tile, h)
    return jax.lax.map(one, (jnp.arange(tiles.shape[0]), tiles)).reshape(xs.shape)


def grouped_gated(xs, groups, n_tiles, gate_w, up_w, down_w, *, tile: int,
                  impl: str):
    """xs [T x tile, h], groups [T] int32, n_tiles () int32, gate_w / up_w
    [G, h, m], down_w [G, m, h] -> [T x tile, h] in ``xs``'s dtype: tile
    ``t``'s rows through group ``groups[t]``'s gated FFN, zeros from tile
    ``n_tiles`` on. ``impl``: "pallas", "interpret" (the kernel off the
    TPU) or "xla"."""
    groups, n_tiles = groups.astype(jnp.int32), n_tiles.astype(jnp.int32)
    if impl == "xla":
        return _xla(xs, groups, n_tiles, gate_w, up_w, down_w, tile)
    return _pallas(xs, groups, n_tiles, gate_w, up_w, down_w, tile,
                   interpret=impl == "interpret")
