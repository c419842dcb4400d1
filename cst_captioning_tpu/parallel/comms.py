"""Gradient communication: bucketed / compressed / overlapped allreduce.

The RL update is the bandwidth-bound program of the step (round-5 record,
removed in PR 21: bw_util 0.45 at MFU 0.20, on code older than PRs 1–20)
and its allreduce is spelled per-leaf — one
``psum`` per parameter array, dozens of small messages per update. This
module centralizes the cross-device gradient reduction behind one knob
surface (``train.comm_*``), applying the *Densifying Assumed-sparse
Tensors* insight (PAPERS.md, arXiv 1905.04035):

- **Bucketing** (``comm_bucket_mb``): the grad tree flattens into
  size-targeted contiguous buckets, ordered by parameter FAMILY
  (``train/mesh.py PARAM_PARTITION_RULES`` order) so the effectively-sparse
  embedding/vocab-projection rows coalesce into dense payloads, and ONE
  ``psum`` runs per bucket instead of per leaf. Elementwise the sum over
  devices is unchanged, so bucketed f32 is BIT-IDENTICAL to the per-leaf
  spelling (pinned in tests/test_comms.py).
- **bf16 on the wire** (``comm_dtype="bf16"``): grads cast to bfloat16
  before the collective and back after, halving bytes-on-wire; parameters
  and Adam moments stay f32 (master accumulation), so per-step rounding
  does not compound in the state. Tolerance-pinned, off by default — the
  f32 path remains the bit-exact reference.
- **Overlap** (``comm_overlap``, rides ``rl.update_chunks``): each chunk's
  grads start their psum while the next chunk's backward runs (the
  double-buffered carry lives in ``rl/scst._block_loss_grads``). The
  bit-exact reference is the EAGER per-chunk-reduce spelling (identical
  float order, no double buffer); note overlap reduces every chunk's full
  param-shaped tree, so its wire volume is (chunks+1)x the unoverlapped
  payload — a latency-hiding trade :func:`ledger` accounts for
  (``reductions=chunks+1``).

``reduce_tree`` is the single entry point the six step/update factories
call inside their shard_map bodies; ``comm=None`` keeps the exact pre-PR
per-leaf spelling. The bucket plan is built host-side at TRACE time (it
depends only on leaf shapes/dtypes), which is also where the per-update
``comm.*`` gauges are set — zero device work is added for observability.
"""

from __future__ import annotations

import re
from dataclasses import dataclass

from cst_captioning_tpu import obs
from cst_captioning_tpu.train.mesh import PARAM_PARTITION_RULES, param_path_names

_WIRE_DTYPES = ("f32", "bf16")
_OVERLAP_MODES = ("off", "defer", "eager")

# bytes-on-wire histogram buckets: 64 KiB .. 64 MiB per message
_BUCKET_BYTES_BUCKETS = tuple(float(1 << s) for s in range(16, 27))


@dataclass(frozen=True)
class CommConfig:
    """How the step/update factories reduce gradients across the mesh.

    ``bucket_mb``  — target payload per collective, in MiB of WIRE bytes;
                     ``0`` disables coalescing (one psum per leaf, still in
                     the wire dtype).
    ``dtype``      — "f32" (bit-exact default) or "bf16" (half the wire
                     bytes; f32 master accumulation in the optimizer).
    ``overlap``    — "off" | "defer" (double-buffered per-chunk reduce,
                     the production overlap) | "eager" (per-chunk reduce
                     with no buffering: defer's bit-exact float-order
                     reference). Only the chunked RL update consumes it.
    """

    bucket_mb: float = 4.0
    dtype: str = "f32"
    overlap: str = "off"

    def __post_init__(self):
        if self.dtype not in _WIRE_DTYPES:
            raise ValueError(
                f"unknown comm dtype {self.dtype!r} "
                f"(expected one of {_WIRE_DTYPES})"
            )
        if self.overlap not in _OVERLAP_MODES:
            raise ValueError(
                f"unknown comm overlap mode {self.overlap!r} "
                f"(expected one of {_OVERLAP_MODES})"
            )
        if self.bucket_mb < 0:
            raise ValueError(f"comm bucket_mb {self.bucket_mb} must be >= 0")

    @classmethod
    def from_train(cls, train) -> "CommConfig":
        """Build from a ``TrainConfig`` (the ``train.comm_*`` knobs)."""
        return cls(
            bucket_mb=train.comm_bucket_mb,
            dtype=train.comm_dtype,
            overlap="defer" if train.comm_overlap else "off",
        )


@dataclass(frozen=True)
class Bucket:
    indices: tuple[int, ...]      # flat-leaf indices (family-ordered)
    wire_dtype: str               # dtype name on the wire
    bytes_on_wire: int            # payload bytes of ONE psum of this bucket


@dataclass(frozen=True)
class BucketPlan:
    buckets: tuple[Bucket, ...]
    n_leaves: int
    bytes_on_wire: int            # total payload bytes per reduction


def _family_rank(path: str) -> int:
    """Position of a param path's family in PARAM_PARTITION_RULES; paths
    matching no rule sort last (stably, by original leaf order)."""
    for rank, (_, pattern, _spec) in enumerate(PARAM_PARTITION_RULES):
        if re.fullmatch(pattern, path):
            return rank
    return len(PARAM_PARTITION_RULES)


def _mp_sharded(path: str) -> bool:
    """Whether MP_PARAM_PARTITION_RULES puts this param on the 'mp' axis
    (flagship-XL: the vocab/out-projection and LSTM gate families)."""
    from cst_captioning_tpu.train.mesh import MP_PARAM_PARTITION_RULES

    for _family, pattern, spec in MP_PARAM_PARTITION_RULES:
        if re.fullmatch(pattern, path):
            return any(a == "mp" for a in spec if a is not None)
    return False


def mp_shard_view(tree, mp_devices: int):
    """The dp-allreduce payload shape under mp sharding, as a ShapeDtype
    pytree: every mp-sharded leaf carries 1/mp of its elements per device
    (the embedding gradient under a row-sharded table stays DENSE — each
    shard reduces its own [V/mp, E] block, never a scatter of sparse
    rows — so it buckets exactly like any other leaf). Host-side analytic
    view for :func:`ledger`; identity at ``mp_devices<=1``."""
    import jax

    if mp_devices <= 1:
        return tree
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    paths = param_path_names(tree)
    out = []
    for path, leaf in zip(paths, leaves):
        if _mp_sharded(path):
            out.append(jax.ShapeDtypeStruct(
                (-(-leaf.size // mp_devices),), leaf.dtype
            ))
        else:
            out.append(jax.ShapeDtypeStruct(leaf.shape, leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, out)


def _wire_dtype_of(leaf, comm: CommConfig):
    """The on-wire dtype for one leaf (host-side; works on tracers and
    ShapeDtypeStructs alike — only ``.dtype`` is read)."""
    import jax.numpy as jnp

    if comm.dtype == "bf16" and jnp.issubdtype(leaf.dtype, jnp.floating):
        return jnp.dtype(jnp.bfloat16)
    return jnp.dtype(leaf.dtype)


def plan_buckets(tree, comm: CommConfig) -> BucketPlan:
    """Family-ordered, size-targeted bucket plan for a grad pytree.

    Host-side and trace-safe: only leaf shapes/dtypes and key paths are
    read. Leaves sort by (family rank, flatten order) — the embedding /
    vocab-projection families coalesce — then pack greedily into buckets of
    at most ``bucket_mb`` MiB of wire bytes; a single leaf larger than the
    target gets its own bucket; only same-wire-dtype leaves share one.
    """
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    paths = param_path_names(tree)
    order = sorted(
        range(len(leaves)), key=lambda i: (_family_rank(paths[i]), i)
    )
    target = int(comm.bucket_mb * (1 << 20))
    buckets: list[Bucket] = []
    cur: list[int] = []
    cur_bytes = 0
    cur_dtype = None

    def flush():
        nonlocal cur, cur_bytes, cur_dtype
        if cur:
            buckets.append(Bucket(
                indices=tuple(cur), wire_dtype=str(cur_dtype),
                bytes_on_wire=cur_bytes,
            ))
        cur, cur_bytes, cur_dtype = [], 0, None

    for i in order:
        wd = _wire_dtype_of(leaves[i], comm)
        nbytes = leaves[i].size * wd.itemsize
        same = cur_dtype is None or str(wd) == cur_dtype
        fits = target <= 0 or not cur or cur_bytes + nbytes <= target
        if not (same and fits):
            flush()
        cur.append(i)
        cur_bytes += nbytes
        cur_dtype = str(wd)
        if target <= 0:
            flush()  # bucket_mb=0: one message per leaf
    flush()
    return BucketPlan(
        buckets=tuple(buckets),
        n_leaves=len(leaves),
        bytes_on_wire=sum(b.bytes_on_wire for b in buckets),
    )


def per_leaf_f32_bytes(tree) -> int:
    """Analytic bytes-on-wire of the pre-PR spelling: one f32-sized psum
    per leaf (the baseline :func:`ledger`'s ``bytes_ratio`` is taken
    against)."""
    import jax

    return sum(
        leaf.size * 4 for leaf in jax.tree_util.tree_leaves(tree)
    )


def _observe_plan(plan: BucketPlan) -> None:
    """Trace-time observability: the per-update comm shape as gauges plus
    a per-message payload histogram. Host-side only — nothing reaches the
    compiled program. Dispatch-level wall-clock spans ride
    ``resilience.health.collective_span`` (wrapped around the update call
    by SCSTTrainer and bench_comms)."""
    obs.gauge("comm.buckets").set(float(len(plan.buckets)))
    obs.gauge("comm.bytes_on_wire").set(float(plan.bytes_on_wire))
    hist = obs.histogram("comm.bucket_bytes", _BUCKET_BYTES_BUCKETS)
    for b in plan.buckets:
        hist.observe(float(b.bytes_on_wire))


def local_params(params, axis: str):
    """``params`` typed as VARYING over mesh axis ``axis`` (call INSIDE a
    shard_map body): differentiating w.r.t. the result yields this shard's
    LOCAL gradient — :func:`reduce_tree`'s input.

    Replicated params are typed invariant, and jax then sums their
    cotangent over the axis on its own (the transpose of the implicit
    invariant->varying cast is a psum). The data-parallel factories own that
    reduction explicitly — bucketed, optionally bf16 on the wire, optionally
    overlapped with the next chunk's backward — so they must differentiate
    w.r.t. the varying copy; reducing the already-summed gradient again
    makes it ``axis_size`` x too large, which Adam's scale invariance hides
    and the global-norm clip, the ``grad_norm`` metric and the divergence
    guard do not (found on four chips in PR 21)."""
    import jax

    return jax.tree.map(
        lambda x: jax.lax.pcast(x, axis, to="varying"), params
    )


def reduce_tree(grads, axis: str, comm: CommConfig | None):
    """Allreduce a gradient pytree over mesh axis ``axis`` (call INSIDE a
    shard_map body).

    ``comm=None`` is the exact pre-PR spelling: one ``psum`` per leaf, no
    cast — kept callable so parity tests can pin the new paths against it.
    Otherwise leaves are packed per :func:`plan_buckets`, each bucket is
    raveled/concatenated into one contiguous buffer in the wire dtype,
    psum'd once, and split back; results cast back to each leaf's dtype.
    psum is elementwise, so at f32 this is bit-identical to per-leaf.
    """
    import jax
    import jax.numpy as jnp

    if comm is None:
        return jax.tree.map(lambda g: jax.lax.psum(g, axis), grads)

    leaves, treedef = jax.tree_util.tree_flatten(grads)
    plan = plan_buckets(grads, comm)
    _observe_plan(plan)
    out: list = [None] * len(leaves)
    for bucket in plan.buckets:
        wd = jnp.dtype(bucket.wire_dtype)
        parts = [leaves[i].reshape(-1).astype(wd) for i in bucket.indices]
        buf = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
        buf = jax.lax.psum(buf, axis)
        offset = 0
        for i in bucket.indices:
            leaf = leaves[i]
            piece = jax.lax.dynamic_slice_in_dim(buf, offset, leaf.size)
            out[i] = piece.reshape(leaf.shape).astype(leaf.dtype)
            offset += leaf.size
    return jax.tree_util.tree_unflatten(treedef, out)


def ledger(tree, comm: CommConfig | None, reductions: int = 1,
           mp_devices: int = 1) -> dict:
    """Host-side bytes-on-wire accounting for one update that reduces a
    ``tree``-shaped payload ``reductions`` times (1 for the fused/chunked
    unoverlapped update; chunks+1 for the overlapped chunked update, which
    reduces every chunk's param-shaped grads plus the encoder cotangent
    fold).

    ``mp_devices>1`` accounts the flagship-XL dp-allreduce: mp-sharded
    leaves (embedding, vocab projection, LSTM gates) reduce only their
    local 1/mp block per device (:func:`mp_shard_view`) — the mp=1 numbers
    are bit-identical to the pre-mp ledger."""
    tree = mp_shard_view(tree, mp_devices)
    if comm is None:
        import jax

        n = len(jax.tree_util.tree_leaves(tree))
        total = per_leaf_f32_bytes(tree)
        return {
            "buckets": n, "messages_per_update": n * reductions,
            "bytes_on_wire_per_update": total * reductions,
        }
    plan = plan_buckets(tree, comm)
    return {
        "buckets": len(plan.buckets),
        "messages_per_update": len(plan.buckets) * reductions,
        "bytes_on_wire_per_update": plan.bytes_on_wire * reductions,
    }
