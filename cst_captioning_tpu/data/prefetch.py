"""Background host->device prefetch.

The reference moves each batch with ``.cuda()`` inline in the hot loop
(SURVEY.md §3.1); here a daemon thread stages upcoming batches into HBM with
``jax.device_put`` while the current step runs, hiding PCIe/host latency —
the flax ``prefetch_to_device`` pattern, generalized to our Batch pytrees and
to explicit shardings (so prefetch lands per-device shards directly when a
Mesh is in play).

The thread serves a whole run of epochs (:class:`PrefetchFeed`): while the
consumer drains one epoch (queued updates, the state's read-back) it stages
the first batches of the next, so that no epoch begins by waiting for a
collate. :func:`prefetch_to_device` is the same feed over a single iterable.

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
    batches staged in the queue (at most ``depth``: an epoch's end marker
    takes a place in the queue and no slot), the one being collated and the
    one the consumer holds, so that in the steady state a slot's last upload
    finished long before its turn comes again and the fence below is a
    formality. That count does not care which epoch a batch belongs to, so
    it holds for a worker that stages across an epoch's end as well.

    The fence: a :class:`PrefetchFeed` (``staging=ring``) reports what it
    placed from the newest slot (:meth:`uploaded`); before that slot is
    handed out again (:meth:`acquire`, on the staging thread) those arrays are
    waited on. A slot whose arrays somebody else can still read or whose
    upload cannot be shown to be over — the placement aliases the host
    memory (the CPU backend does, for 64-byte-aligned arrays), a placed
    array was deleted or donated — is not rewritten: the batch keeps those
    arrays and the slot starts over with fresh ones. A slot that was collated
    but never placed (a batch skipped on resume) is simply reused.

    One ring serves one staging thread at a time (``acquire`` and
    ``uploaded`` alternate on it): one live feed, which joins a worker before
    it starts the next. The worker settles the ring when it retires (the
    run's last epoch staged, ``stop_event``, an error, staged batches
    dropped), not at every epoch's end: between epochs the fence before each
    slot's reuse is the safety, as it is within one."""

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


class _Mark:
    """What the staging thread queues beside the staged items."""


class _Failed(_Mark):
    """In place of the item the staging thread failed on: the consumer
    raises it when it comes to that item."""

    def __init__(self, error: BaseException):
        self.error = error


_END = _Mark()      # an epoch's items are all staged
_HALT = _Mark()     # the worker stopped on ``stop_event``: nothing follows


class _Worker:
    """One staging thread of a :class:`PrefetchFeed` and what is its own:
    the queue it fills and the flag that tells it the consumer is gone (its
    own, so that a worker which outlived its join cannot fill the queue of
    the worker started after it)."""

    def __init__(self, size: int):
        self.q: queue.Queue = queue.Queue(maxsize=size)
        self.gone = threading.Event()
        self.unqueued = 0   # items staged that found the consumer gone
        self.thread: threading.Thread | None = None

    def put(self, x) -> bool:
        """put that gives up when the consumer abandoned the worker."""
        while not self.gone.is_set():
            try:
                self.q.put(x, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False


class PrefetchFeed:
    """One background staging worker for a run of epochs.

    ``draw(key)`` gives the items of the epoch ``key`` names (it is called,
    and iterated, on the staging thread); ``following(key)`` names the epoch
    after it, or None where the run ends. The consumer asks for one epoch at
    a time (:meth:`epoch`) and gets an iterator that ends with that epoch's
    last item. The worker does not end there: having staged an epoch's last
    item it goes on to the first items of ``following(key)``, held back only
    by the queue (``size`` items staged, one more in its hands, whether or
    not an epoch's end lies among them), so that the next epoch does not
    begin by waiting for a collate and an upload. It retires (joined, the
    staging ring settled) where ``following`` says the run ends, when
    ``stop_event`` is set, on an error, when an epoch's iterator is closed
    or dropped before its end, and in :meth:`close`.

    What was staged ahead is good for the epoch it was made for and no other:
    when the next epoch asked for is not the ``following`` of the last one
    (``key`` compares unequal), the staged items are dropped, the ring is
    settled and a new worker starts on the epoch asked for, through the same
    code as a run's first epoch. Keys are compared, never inspected: put in
    one whatever decides an epoch's items and their placement.

    An error belongs to the item it happened on: it is raised when the
    consumer comes to that item, after everything staged before it, be that
    in the epoch during which the worker hit it or in the next.

    Counters: ``prefetch.epoch.carried`` / ``prefetch.epoch.cold`` (epochs
    that found the worker already on them / that started one, or ran
    inline), ``prefetch.dropped`` (items staged and thrown away).

    ``size``, ``sharding``, ``transform``, ``place``, ``stall_warn_s`` and
    ``staging`` are :func:`prefetch_to_device`'s. ``size`` < 1 stages inline
    in the consumer's thread: no worker, nothing ahead.
    """

    def __init__(
        self,
        draw: Callable[[Any], Iterable[Any]],
        following: Callable[[Any], Any] | None = None,
        *,
        size: int = 2,
        sharding: Any | None = None,
        transform: Callable[[Any], Any] | None = None,
        place: bool = True,
        stop_event: threading.Event | None = None,
        stall_warn_s: float = 5.0,
        staging: StagingRing | None = None,
    ):
        self._draw = draw
        self._following = following if following is not None else lambda key: None
        self._size = size
        self._transform = transform
        if not place:
            self._place = lambda x: x
        elif sharding is not None:
            self._place = lambda x: jax.device_put(x, sharding)
        else:
            self._place = jax.device_put
        self._stop_event = stop_event
        self._stall_warn_s = stall_warn_s
        self._staging = staging
        # the queue depth the consumer sees: pinned at 0 it is the
        # "input-bound" smoking gun next to a fat prefetch.wait in the report
        self._depth = obs.gauge("prefetch.queue_depth")
        self._worker: _Worker | None = None
        self._ahead: Any = None     # the epoch the live worker stages next

    # ---- the staging thread's side ------------------------------------------

    def _h2d(self, x):
        def put():
            chaos.visit("prefetch.h2d")
            return self._place(x)

        return retry_call(
            put,
            policy=_H2D_RETRY,
            on_retry=lambda info: (
                obs.counter("resilience.h2d_retry").inc(),
                obs.event("h2d_retry", **info),
            ),
        )

    def _stage(self, items: Iterator[Any]):
        """Pull the next item of ``items`` and stage it; ``_END`` when they
        are exhausted. One ``prefetch.stage`` span a batch, on the staging
        thread's own trace track, from before the pull (upstream's collate
        runs in it) to after the upload; the pull that finds the epoch at
        its end records none. ``prefetch.h2d`` inside it covers
        ``transform`` and the placement: the *enqueue* of ``device_put`` /
        ``put_global``, not the transfer's completion (no sync is added to
        learn that)."""
        stage = obs.span("prefetch.stage").begin()
        leave = stage.end
        try:
            try:
                x = next(items)
            except StopIteration:
                leave = stage.cancel
                return _END
            x = chaos.visit("prefetch.stage", x)
            with obs.span("prefetch.h2d"):
                x = self._transform(x) if self._transform is not None else x
                x = self._h2d(x)
            if self._staging is not None:
                self._staging.uploaded(x)
            return x
        finally:
            leave()

    def _settle(self) -> None:
        if self._staging is not None:
            self._staging.settle()

    def _work(self, w: _Worker, key: Any) -> None:
        try:
            items = None
            while not w.gone.is_set():
                if self._stop_event is not None and self._stop_event.is_set():
                    # preempting: what is staged is still the consumer's,
                    # nothing more is collated, no further epoch is begun
                    w.put(_HALT)
                    return
                if items is None:
                    items = iter(self._draw(key))
                x = self._stage(items)
                if x is _END:
                    key, items = self._following(key), None
                    if not w.put(_END) or key is None:
                        return
                elif not w.put(x):
                    w.unqueued += 1     # consumer gone: free the buffers
                    return
                self._depth.set(w.q.qsize())
        except BaseException as e:  # propagate into the consumer, in its place
            w.put(_Failed(e))
        finally:
            self._settle()

    # ---- the consumer's side ------------------------------------------------

    def _get(self, w: _Worker):
        """q.get that reports (once per episode) when the worker starves the
        step loop past ``stall_warn_s`` — the wedged-prefetch signature."""
        if self._stall_warn_s <= 0:
            return w.q.get()
        reported = False
        waited = 0.0
        while True:
            try:
                return w.q.get(timeout=self._stall_warn_s)
            except queue.Empty:
                waited += self._stall_warn_s
                if not reported:
                    reported = True
                    obs.counter("resilience.prefetch_stall").inc()
                    obs.event(
                        "prefetch_stall",
                        waited_s=round(waited, 3),
                        queue_depth=w.q.qsize(),
                        worker_alive=w.thread.is_alive(),
                    )

    def epoch(self, key: Any) -> Iterator[Any]:
        """The staged items of the epoch ``key`` names, ending with its last.
        One epoch at a time: ask for the next when this iterator has ended
        or been closed."""
        if self._size < 1:
            obs.counter("prefetch.epoch.cold").inc()
            items = iter(self._draw(key))
            try:
                while (x := self._stage(items)) is not _END:
                    yield x
            finally:
                self._settle()
            return

        w = self._worker
        if w is not None and key == self._ahead:
            obs.counter("prefetch.epoch.carried").inc()
        else:
            self.close()    # what was staged ahead was made for another epoch
            obs.counter("prefetch.epoch.cold").inc()
            w = self._worker = _Worker(self._size)
            w.thread = threading.Thread(
                target=self._work, args=(w, key), daemon=True, name="prefetch",
            )
            w.thread.start()
        ended = False
        try:
            while True:
                # the consumer standing still for the input pipeline: every
                # get, the one that returns an epoch's end marker included
                with obs.span("prefetch.wait"):
                    x = self._get(w)
                # depth as the CONSUMER sees it post-get: 0 here while the
                # worker is mid-stage means the step loop is input-bound
                self._depth.set(w.q.qsize())
                if x is _END:
                    ended = True
                    return
                if x is _HALT:
                    return
                if isinstance(x, _Failed):
                    raise x.error
                yield x
        finally:
            # the worker carries on only past an epoch that was consumed to
            # its end, and only where the run has a next one; a consumer that
            # broke out early (or errored) retires it with what it staged
            self._ahead = self._following(key) if ended else None
            if self._ahead is None:
                self.close()

    def close(self) -> None:
        """Retire the worker: unblock it, throw away what it staged, join
        it. It settles the ring on its way out. Safe to call twice."""
        w, self._worker, self._ahead = self._worker, None, None
        if w is None:
            return
        w.gone.set()
        dropped = 0
        while True:
            try:
                x = w.q.get_nowait()
            except queue.Empty:
                break
            dropped += not isinstance(x, _Mark)
        w.thread.join(timeout=2.0)
        dropped += w.unqueued
        if dropped:
            obs.counter("prefetch.dropped").inc(dropped)


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
    """Iterate ``it``, staging ``size`` elements ahead onto device: a
    :class:`PrefetchFeed` whose run is the one epoch ``it``. The worker
    starts with the first item asked for and retires with the last.

    ``transform`` runs on the host thread before the transfer (e.g. Batch ->
    device-ready pytree); ``sharding`` is forwarded to ``jax.device_put`` so
    multi-device layouts are materialized without a separate reshard.

    ``place=False`` skips the internal ``device_put`` — for items that mix
    device arrays with host-only leaves (e.g. video-id strings for the RL
    reward), ``transform`` does its own placement of the array part.

    ``stop_event`` (optional) makes the staging thread quit before its next
    collate/transfer once set — the preemption path: when SIGTERM lands, the
    grace window should go to the checkpoint fsync, not to prefetching
    batches that will never run. Items already staged are still yielded, and
    the iterator ends after them.

    ``stall_warn_s``: when the consumer waits longer than this on an empty
    queue while the worker is still alive (a wedged prefetch thread, a
    stalled filesystem read), a structured ``prefetch_stall`` event and the
    ``resilience.prefetch_stall`` counter fire once per stall episode —
    starvation becomes diagnosable instead of looking like slow compute.
    The consumer keeps waiting (the worker may unwedge); 0 disables.

    ``staging``: the :class:`StagingRing` that ``it`` collates into
    (``Batcher.epoch(..., staging=ring)``). Every placement is reported to it,
    and when the worker retires the ring is settled. Who may keep what: the
    yielded (placed) items are the consumer's for as long as it likes; the
    host arrays of a staged ``Batch`` are the ring's, and ``transform`` must
    not let them through un-placed unless it is content with the ring
    noticing and giving that slot up.
    """
    return PrefetchFeed(
        lambda key: it, size=size, sharding=sharding, transform=transform,
        place=place, stop_event=stop_event, stall_warn_s=stall_warn_s,
        staging=staging,
    ).epoch(0)
