"""Background host->device prefetch.

The reference moves each batch with ``.cuda()`` inline in the hot loop
(SURVEY.md §3.1); here a daemon thread stages upcoming batches into HBM with
``jax.device_put`` while the current step runs, hiding PCIe/host latency —
the flax ``prefetch_to_device`` pattern, generalized to our Batch pytrees and
to explicit shardings (so prefetch lands per-device shards directly when a
Mesh is in play).

The host side of a staged batch is a slot of a :class:`StagingRing`: the
training loops collate every batch into memory that is reused, and rewritten
only once the upload made from it is known to be over (the fence). What is
yielded (the placed arrays) is the consumer's to keep; the host arrays
behind it stay the ring's.
"""

from __future__ import annotations

import queue
import threading
from typing import Any, Callable, Iterable, Iterator

import jax
import numpy as np

from cst_captioning_tpu import obs
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.resilience.chaos import TransientIOError
from cst_captioning_tpu.resilience.retry import RetryPolicy, retry_call

# transient H2D transfer failures (a torn DMA / chaos partial_h2d) are
# redone in place under a tight budget: the staged numpy batch is still on
# host, so re-placing it is always safe. Anything non-transient propagates.
_H2D_RETRY = RetryPolicy(
    max_attempts=3, base_delay=0.01, max_delay=0.1, budget=1.0,
    retry_on=(TransientIOError,),
)


class StagingRing:
    """Host staging slots that a batch is collated into, uploaded from, and
    that are rewritten a few batches later: a batch then costs one copy of its
    bytes and no new pages.

    A slot is a dict the collate keeps its arrays in (``Batcher.epoch(...,
    staging=ring)`` fills it; this class never looks inside but to see whose
    memory a placed array reads). The ring holds ``depth`` + 2 of them: the
    batches staged in the queue, the one being collated and the one the
    consumer holds, so that in the steady state a slot's last upload finished
    long before its turn comes again and the fence below is a formality.

    The fence: ``prefetch_to_device(..., staging=ring)`` reports what it
    placed from the newest slot (:meth:`uploaded`); before that slot is
    handed out again (:meth:`acquire`, on the staging thread) those arrays are
    waited on. A slot whose arrays somebody else can still read or whose
    upload cannot be shown to be over — the placement aliases the host
    memory (the CPU backend does, for 64-byte-aligned arrays), a placed
    array was deleted or donated — is not rewritten: the batch keeps those
    arrays and the slot starts over with fresh ones. A slot that was collated
    but never placed (a batch skipped on resume) is simply reused.

    One ring serves one live ``prefetch_to_device`` at a time."""

    def __init__(self, depth: int):
        self._slots: list[dict] = [{} for _ in range(max(depth, 0) + 2)]
        self._pending: list[list | None] = [None] * len(self._slots)
        self._turn = -1

    def acquire(self) -> dict:
        """The next slot, safe to rewrite (empty if it has to start over)."""
        self._turn = k = (self._turn + 1) % len(self._slots)
        self._fence(k)
        return self._slots[k]

    def uploaded(self, placed: Any) -> None:
        """``placed`` (any pytree) is what was put on the device from the
        slot acquired last."""
        k = self._turn
        spans = [
            (a.ctypes.data, a.ctypes.data + a.nbytes)
            for a in jax.tree.leaves(self._slots[k])
            if isinstance(a, np.ndarray)
        ]
        pending = []
        for leaf in jax.tree.leaves(placed):
            if _reads_host(leaf, spans):
                self._slots[k] = {}     # the placed batch owns those arrays now
                return
            if hasattr(leaf, "block_until_ready"):
                pending.append(leaf)
        self._pending[k] = pending

    def settle(self) -> None:
        """Wait for every reported upload and let go of the placed arrays
        (they would otherwise hold their device memory until the slot's next
        turn)."""
        for k in range(len(self._slots)):
            self._fence(k)

    def _fence(self, k: int) -> None:
        pending, self._pending[k] = self._pending[k], None
        if not pending:
            return
        with obs.span("prefetch.fence"):
            try:
                for leaf in pending:
                    leaf.block_until_ready()
            except RuntimeError:
                # deleted or donated since: the runtime may still be reading
                # the slot's memory, and nothing is left to wait on
                self._slots[k] = {}


def _reads_host(leaf: Any, spans: list[tuple[int, int]]) -> bool:
    """Does ``leaf`` live in the host memory of ``spans``: a numpy view of
    it, or a placed array of a backend that took the memory over instead of
    copying it."""
    if isinstance(leaf, np.ndarray):
        starts = [leaf.ctypes.data]
    elif isinstance(leaf, jax.Array) and not leaf.is_deleted():
        starts = [
            s.data.unsafe_buffer_pointer()
            for s in leaf.addressable_shards
            if s.device.platform == "cpu"
        ]
    else:
        return False
    return any(lo <= p < hi for p in starts for lo, hi in spans)


def prefetch_to_device(
    it: Iterable[Any],
    size: int = 2,
    sharding: Any | None = None,
    transform: Callable[[Any], Any] | None = None,
    place: bool = True,
    stop_event: threading.Event | None = None,
    stall_warn_s: float = 5.0,
    staging: StagingRing | None = None,
) -> Iterator[Any]:
    """Iterate ``it``, staging ``size`` elements ahead onto device.

    ``transform`` runs on the host thread before the transfer (e.g. Batch ->
    device-ready pytree); ``sharding`` is forwarded to ``jax.device_put`` so
    multi-device layouts are materialized without a separate reshard.

    ``place=False`` skips the internal ``device_put`` — for items that mix
    device arrays with host-only leaves (e.g. video-id strings for the RL
    reward), ``transform`` does its own placement of the array part.

    ``stop_event`` (optional) makes the staging thread quit before its next
    collate/transfer once set — the preemption path: when SIGTERM lands, the
    grace window should go to the checkpoint fsync, not to prefetching
    batches that will never run. Items already staged are still yielded.

    ``stall_warn_s``: when the consumer waits longer than this on an empty
    queue while the worker is still alive (a wedged prefetch thread, a
    stalled filesystem read), a structured ``prefetch_stall`` event and the
    ``resilience.prefetch_stall`` counter fire once per stall episode —
    starvation becomes diagnosable instead of looking like slow compute.
    The consumer keeps waiting (the worker may unwedge); 0 disables.

    ``staging``: the :class:`StagingRing` that ``it`` collates into
    (``Batcher.epoch(..., staging=ring)``). Every placement is reported to it,
    and when staging ends the ring is settled. Who may keep what: the yielded
    (placed) items are the consumer's for as long as it likes; the host
    arrays of a staged ``Batch`` are the ring's, and ``transform`` must not
    let them through un-placed unless it is content with the ring noticing
    and giving that slot up.
    """
    if not place:
        _place = lambda x: x
    elif sharding is not None:
        _place = lambda x: jax.device_put(x, sharding)
    else:
        _place = jax.device_put

    # the queue depth the consumer sees: pinned at 0 it is the "input-bound"
    # smoking gun next to a fat prefetch.wait in the run report
    depth = obs.gauge("prefetch.queue_depth")
    it = iter(it)
    _END = object()

    def _h2d(x):
        def put():
            chaos.visit("prefetch.h2d")
            return _place(x)

        return retry_call(
            put,
            policy=_H2D_RETRY,
            on_retry=lambda info: (
                obs.counter("resilience.h2d_retry").inc(),
                obs.event("h2d_retry", **info),
            ),
        )

    def _stage():
        """Pull the next item from upstream and stage it; ``_END`` when
        upstream is exhausted. One ``prefetch.stage`` span a batch, on the
        staging thread's own trace track, from before the pull (upstream's
        collate runs in it) to after the upload; the pull that finds
        upstream exhausted records none. ``prefetch.h2d`` inside it covers
        ``transform`` and the placement: the *enqueue* of ``device_put`` /
        ``put_global``, not the transfer's completion (no sync is added to
        learn that)."""
        stage = obs.span("prefetch.stage").begin()
        leave = stage.end
        try:
            try:
                x = next(it)
            except StopIteration:
                leave = stage.cancel
                return _END
            x = chaos.visit("prefetch.stage", x)
            with obs.span("prefetch.h2d"):
                x = transform(x) if transform is not None else x
                x = _h2d(x)
            if staging is not None:
                staging.uploaded(x)
            return x
        finally:
            leave()

    def _retire():
        if staging is not None:
            staging.settle()

    if size < 1:
        try:
            while (x := _stage()) is not _END:
                yield x
        finally:
            _retire()
        return

    q: queue.Queue = queue.Queue(maxsize=size)
    err: list[BaseException] = []
    stop = threading.Event()

    def _put(x) -> bool:
        """put that gives up when the consumer abandoned the generator."""
        while not stop.is_set():
            try:
                q.put(x, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            while True:
                if stop_event is not None and stop_event.is_set():
                    return  # preempting: yield only what's already staged
                x = _stage()
                if x is _END:
                    return
                if not _put(x):
                    return  # consumer gone: drop staged work, free buffers
                depth.set(q.qsize())
        except BaseException as e:  # propagate into the consumer
            err.append(e)
        finally:
            _put(_END)
            _retire()

    def _get_with_stall_watchdog():
        """q.get that reports (once per episode) when the worker starves the
        step loop past ``stall_warn_s`` — the wedged-prefetch signature."""
        if stall_warn_s <= 0:
            return q.get()
        reported = False
        waited = 0.0
        while True:
            try:
                return q.get(timeout=stall_warn_s)
            except queue.Empty:
                waited += stall_warn_s
                if not reported:
                    reported = True
                    obs.counter("resilience.prefetch_stall").inc()
                    obs.event(
                        "prefetch_stall",
                        waited_s=round(waited, 3),
                        queue_depth=q.qsize(),
                        worker_alive=t.is_alive(),
                    )

    t = threading.Thread(target=worker, daemon=True, name="prefetch")
    t.start()
    try:
        while True:
            # the consumer standing still for the input pipeline: every
            # get, the one that returns the end marker included
            with obs.span("prefetch.wait"):
                x = _get_with_stall_watchdog()
            # depth as the CONSUMER sees it post-get: 0 here while the
            # worker is mid-stage means the step loop is input-bound
            depth.set(q.qsize())
            if x is _END:
                if err:
                    raise err[0]
                return
            yield x
    finally:
        # consumer broke out early (or errored): unblock and retire the worker
        stop.set()
        while True:
            try:
                q.get_nowait()
            except queue.Empty:
                break
        t.join(timeout=2.0)
