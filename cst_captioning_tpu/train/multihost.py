"""Multi-host distributed support: DP/SP past one host, over ICI + DCN.

The reference scaled with single-node ``torch.nn.DataParallel`` (NCCL
underneath — SURVEY.md §2 parallelism inventory, §5 dist-comm row). This
module is the multi-HOST extension the reference never had: each process
(host) runs the same program, ``jax.distributed.initialize`` forms the
global device set, and the jitted shard_map steps are IDENTICAL to the
single-host ones — XLA routes the gradient psums over ICI within a host and
DCN across hosts, exactly the mesh-axis layering SURVEY.md §5 reserved.

The host-side contract (the part XLA cannot do for us):

- **Input**: every process feeds only its own rows.
  :class:`~cst_captioning_tpu.data.batcher.Batcher` with
  ``host_shard=(process_index, process_count)`` deterministically slices the
  same global batch order (the shuffle is keyed by (seed, epoch), so all
  hosts agree without communicating); :func:`put_global` assembles the
  per-process rows into one globally-sharded array.
- **Output**: device results that the host must read (decoded tokens for
  the RL reward or eval) come back via :func:`to_host_local` (this host's
  rows only — the per-host reward path) or :func:`allgather_to_host`
  (replicated everywhere — eval needs every caption).

Single-process behavior is the identity: every helper degrades to the plain
device_put / np.asarray path, so the Trainer wiring is exercised by the
regular test suite and the 2-process parity test
(tests/test_multihost.py) pins multi == single numerically.
"""

from __future__ import annotations

import json
import os

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# DCN-stall probe (resilience/health.py): every cross-host barrier/broadcast
# below runs inside collective_span — a dcn.collective span + histogram, a
# structured dcn_stall event past the threshold, and a piggybacked liveness
# refresh on the active HealthMonitor (a completed collective proves every
# peer was alive). Single-process paths return before the span.
from cst_captioning_tpu.resilience.health import collective_span

# NOTE: jax.experimental.multihost_utils must NOT be imported at module
# level: importing it initializes the XLA backend, after which a later
# jax.distributed.initialize silently degrades to a single-process cluster
# (observed empirically: procs=1, XLA_FLAGS ignored). It is imported lazily
# inside the helpers, all of which run long after initialization.


def _looks_multiworker() -> bool:
    """True only for env markers that UNAMBIGUOUSLY mean this process is one
    worker of a multi-worker accelerator job (multi-host TPU pods).

    ``TPU_WORKER_HOSTNAMES`` counts: single-worker setups set it to one host
    (observed: 'localhost'), where auto-initialize would demand a
    coordinator and fail. Scheduler vars like SLURM_NTASKS /
    OMPI_COMM_WORLD_SIZE are deliberately NOT hints: they are also set for
    single-process runs inside an allocation (tasks reserved for dataloaders
    etc.) — SLURM/MPI users pass the explicit JAX_* env vars instead.
    """
    hosts = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    if len([h for h in hosts.split(",") if h.strip()]) > 1:
        return True
    return bool(os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"))


def initialize(coordinator_address: str | None = None,
               num_processes: int | None = None,
               process_id: int | None = None) -> None:
    """``jax.distributed.initialize`` wrapper.

    With no arguments, initializes only when the standard env vars are set
    (``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``,
    or a TPU-pod environment where JAX auto-detects everything); a plain
    single-host run is untouched. Safe to call twice (second call no-ops).
    """
    # NOTE: must not touch jax.process_count()/jax.devices() here — any
    # backend-initializing call before jax.distributed.initialize is an error
    if jax.distributed.is_initialized():
        return
    coordinator_address = coordinator_address or os.environ.get(
        "JAX_COORDINATOR_ADDRESS"
    )
    env_n = os.environ.get("JAX_NUM_PROCESSES")
    env_i = os.environ.get("JAX_PROCESS_ID")
    if num_processes is None and env_n is not None:
        num_processes = int(env_n)
    if process_id is None and env_i is not None:
        process_id = int(env_i)
    if coordinator_address is None and num_processes is None:
        # no explicit cluster spec: hand off to jax's auto-detection ONLY in
        # unambiguously multi-worker environments (a single-host run must
        # not risk a coordinator connect attempt). A failure here must
        # PROPAGATE: degrading one worker of a real pod to an independent
        # single-host run would corrupt the shared log/checkpoint paths
        if _looks_multiworker():
            jax.distributed.initialize()
            return
        # scheduler says multiple tasks but no JAX_* cluster spec: each rank
        # would train independently and race the shared checkpoint dir —
        # make the misconfiguration loud (we deliberately don't auto-init
        # from these vars; see _looks_multiworker)
        for var in ("SLURM_NTASKS", "OMPI_COMM_WORLD_SIZE"):
            val = os.environ.get(var, "")
            if val.isdigit() and int(val) > 1:
                import logging

                logging.getLogger(__name__).warning(
                    "%s=%s but no JAX_COORDINATOR_ADDRESS/JAX_NUM_PROCESSES/"
                    "JAX_PROCESS_ID set: every rank will run SINGLE-HOST on "
                    "the full dataset and race shared output paths. Pass the "
                    "JAX_* env vars to form one cluster.", var, val,
                )
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
    )


def is_multiprocess() -> bool:
    return jax.process_count() > 1


def assert_seq_axis_within_host(device_grid) -> None:
    """Reject a 2-D ``('data','seq')`` device grid whose seq rows span
    processes.

    Host-sharded batch feeding partitions the 'data' axis by process; a seq
    row spanning hosts would psum frame shards of DIFFERENT videos — silent
    divergence (reproduced on a real 2-process cluster). Checks the ACTUAL
    device placement, not a local-count proxy: device-id order need not be
    process-contiguous on every topology.
    """
    for row in device_grid:
        procs = {d.process_index for d in row}
        if len(procs) > 1:
            raise ValueError(
                f"the mesh's 'seq' axis spans processes ({sorted(procs)}); "
                "pick mesh.seq_devices so every seq row stays on one host "
                "(host-sharded feeding partitions 'data' by process)"
            )


def host_shard() -> tuple[int, int]:
    """(process_index, process_count) — the Batcher ``host_shard`` argument."""
    return jax.process_index(), jax.process_count()


def put_global(shardings, local_tree):
    """Per-process rows -> globally sharded arrays.

    ``shardings`` is a NamedSharding pytree (a tree prefix of
    ``local_tree``); each process passes ONLY its own rows and the result is
    the global array every jitted step sees. Single-process this is exactly
    ``jax.device_put``.
    """
    if not is_multiprocess():
        return jax.device_put(local_tree, shardings)
    return _map_prefix(
        lambda s, x: jax.make_array_from_process_local_data(s, np.asarray(x)),
        shardings, local_tree,
    )


def put_full_global(shardings, full_tree):
    """Every-process-identical host arrays -> globally sharded arrays.

    The eval path: each process iterates the SAME (unsharded) batches, so
    the local data already has the global shape; passing ``global_shape``
    tells jax the input is fully replicated and only this process's shards
    should be extracted. Single-process this is exactly ``jax.device_put``.
    """
    if not is_multiprocess():
        return jax.device_put(full_tree, shardings)

    def put(s, x):
        # typed PRNG keys (TrainState.rng) can't pass through the raw-array
        # assembly; round-trip via their uint32 key data
        if hasattr(x, "dtype") and jax.dtypes.issubdtype(
            x.dtype, jax.dtypes.prng_key
        ):
            # explicit readback (not np.asarray): this is a deliberate,
            # once-per-restore host staging hop, and GL013 holds the hot
            # paths to zero implicit device→host conversions
            data = jax.device_get(jax.random.key_data(x))
            g = jax.make_array_from_process_local_data(
                s, data, global_shape=data.shape
            )
            return jax.random.wrap_key_data(g)
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(
            s, x, global_shape=x.shape
        )

    return _map_prefix(put, shardings, full_tree)


def _map_prefix(fn, shardings, tree):
    """Apply ``fn(sharding, leaf)`` with device_put's tree-prefix broadcast:
    a single sharding applies to every leaf below it."""

    def rec(s, x):
        if isinstance(s, jax.sharding.Sharding):
            return jax.tree.map(lambda leaf: fn(s, leaf), x)
        if isinstance(x, dict):
            return {k: rec(s[k], x[k]) for k in x}
        return type(x)(rec(si, xi) for si, xi in zip(s, x))

    return rec(shardings, tree)


def to_host_local(arr, mesh: Mesh, spec: P) -> np.ndarray:
    """Sharded global array -> THIS process's rows as numpy (per-host reward
    path). Single-process: plain ``np.asarray``."""
    if not is_multiprocess():
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    local = multihost_utils.global_array_to_host_local_array(arr, mesh, spec)
    return np.asarray(local)


def from_host_local(arr, mesh: Mesh, spec: P):
    """THIS process's rows -> sharded global array (advantage upload).

    Single-process: an explicit sharded ``device_put`` — handing the jitted
    update a single-device array instead would make XLA re-scatter it
    device-to-device at EVERY dispatch (an implicit per-batch transfer the
    sanitizer gate vetoes)."""
    if not is_multiprocess():
        return jax.device_put(arr, jax.sharding.NamedSharding(mesh, spec))
    from jax.experimental import multihost_utils

    return multihost_utils.host_local_array_to_global_array(
        np.asarray(arr), mesh, spec
    )


def allgather_to_host(arr) -> np.ndarray:
    """Sharded global array -> full array on EVERY process (eval gather).
    Single-process: plain ``np.asarray``."""
    if not is_multiprocess():
        return np.asarray(arr)
    from jax.experimental import multihost_utils

    with collective_span("allgather_to_host"):
        return np.asarray(multihost_utils.process_allgather(arr, tiled=True))


def global_scalar_mean(x: float) -> float:
    """Mean of a host-side scalar across processes (one tiny collective) —
    for epoch-level stats whose per-step values are per-host (the RL reward).
    Single-process: the identity."""
    if not is_multiprocess():
        return float(x)
    from jax.experimental import multihost_utils

    with collective_span("global_scalar_mean"):
        return float(
            np.mean(
                multihost_utils.process_allgather(np.asarray(x, np.float64))
            )
        )


def allgather_pyobj(obj) -> list:
    """One JSON-serializable host object per process -> every process gets
    ``[obj_0, ..., obj_{P-1}]`` in process order. Two tiny collectives (byte
    lengths, then max-padded utf-8 bytes) regardless of payload structure —
    the host-sharded eval's once-per-split caption merge. Single-process:
    ``[obj]``."""
    if not is_multiprocess():
        return [obj]
    from jax.experimental import multihost_utils

    with collective_span("allgather_pyobj"):
        data = np.frombuffer(
            json.dumps(obj, default=float).encode("utf-8"), dtype=np.uint8
        )
        lengths = np.asarray(
            multihost_utils.process_allgather(np.asarray(data.size, np.int64))
        ).reshape(-1)
        padded = np.zeros((int(lengths.max()),), np.uint8)
        padded[: data.size] = data
        rows = np.asarray(multihost_utils.process_allgather(padded))
        return [
            json.loads(rows[i, : int(lengths[i])].tobytes().decode("utf-8"))
            for i in range(rows.shape[0])
        ]


def broadcast_pyobj(obj):
    """Process 0's JSON-serializable object -> every process (the sharded
    eval's metric fan-out: one process scores, the rest receive). Non-zero
    processes' ``obj`` is ignored. Single-process: the object itself."""
    if not is_multiprocess():
        return obj
    return allgather_pyobj(obj if jax.process_index() == 0 else None)[0]


def global_weighted_mean(value_sum: float, weight: float) -> float:
    """``sum(value_sum)/sum(weight)`` across processes (one tiny collective):
    the exact cross-host mean when hosts contribute unequal row counts (e.g.
    wrap-padded final RL batches). Single-process: the local ratio.
    A zero total weight returns 0.0 (fractional weights stay undistorted)."""
    if not is_multiprocess():
        total_v, total_w = float(value_sum), float(weight)
    else:
        from jax.experimental import multihost_utils

        with collective_span("global_weighted_mean"):
            pair = multihost_utils.process_allgather(
                np.asarray([value_sum, weight], np.float64)
            )
        total = np.sum(np.asarray(pair).reshape(-1, 2), axis=0)
        total_v, total_w = float(total[0]), float(total[1])
    return total_v / total_w if total_w > 0.0 else 0.0
