"""Fixed-shape batch construction.

The reference collates variable-length captions by padding to the batch max
(SURVEY.md §3.4). On TPU that would retrace/recompile per batch shape, so here
EVERY batch is padded to the static ``(batch_size, max_len)`` /
``(batch_size, max_frames, dim)`` envelope — XLA compiles each program once.

Two iteration modes:

- ``mode="caption"`` (XE phase): one row per (video, caption) pair,
  ``seq_per_vid`` captions sampled per video per epoch.
- ``mode="video"`` (RL decode / eval): one row per video; caption slots carry
  an arbitrary GT row (unused by decoding).

Short final batches are wrapped (circular) with a ``valid`` row mask so shapes
stay static while eval stays exact.

Shuffling is keyed by ``(seed, epoch_index)`` — not a running RNG stream — so
a resumed run that sets :attr:`Batcher.epoch_index` from the checkpoint epoch
reproduces the exact batch order of an uninterrupted run (SURVEY.md §3.5
resume semantics, hardened with determinism the reference never had). An
epoch's order is therefore known before the epoch before it has been
trained: the training loops' prefetch worker draws ahead, handing
``epoch()`` the index (and salt) it draws with instead of reading the
attribute the main thread pins.

Where a batch's bytes come from and go to: with ``data.cache_features`` the
features come out of the dataset's contiguous table in one gather a stream,
else out of h5 a row at a time; labels, mask and weights come out of the
dataset's label table (``CaptionDataset.label_table``, built once for the
batcher's ``max_len`` by the thread that collates the first batch) in one
gather each, on either path. A feature gather of at least two blocks of
``_BLOCK_BYTES`` is cut into row blocks that run on the process's one gather
pool (``_gather_pool``: made on first use, owned by this module, as wide as
a share of the cores the process may use); the collating thread waits for
every block inside its one ``data.collate`` span, so the pool's threads open
no span of their own. A smaller one (a batch under 2 x 16 MiB a stream,
every frame mask, any gather on a machine too small for a pool) is one call
on the collating thread, as every label gather is. The bytes go into arrays
the ``Batch`` owns, or, when ``epoch`` was handed a staging ring, into the
ring's next slot. Who may
keep a ``Batch``'s arrays: whoever drew it by plain iteration (``iter``,
``epoch()``), for good; a ``Batch`` drawn with ``epoch(staging=ring)`` only
until the next one is drawn: the ring then owns them (data/prefetch.py).
Which bytes a batch holds never depends on any of this.
"""

from __future__ import annotations

import itertools
import os
import threading
from concurrent.futures import Future, ThreadPoolExecutor, wait
from dataclasses import dataclass
from typing import NamedTuple

import numpy as np

from cst_captioning_tpu import obs
from cst_captioning_tpu.data.dataset import CaptionDataset


@dataclass
class Batch:
    feats: dict[str, np.ndarray]       # name -> [B, F, D] float32
    feat_masks: dict[str, np.ndarray]  # name -> [B, F]    float32
    labels: np.ndarray                 # [B, T] int32: word ids + EOS, then PAD
    mask: np.ndarray                   # [B, T] float32: 1 on real tokens incl. EOS
    weights: np.ndarray                # [B]    float32: WXE consensus weights
    valid: np.ndarray                  # [B]    bool: False on wrap-padding rows
    video_ids: list[str]

    @property
    def size(self) -> int:
        return int(self.valid.sum())


# No row block of a pooled gather is smaller than this, and a gather under
# two of them is one call on the collating thread: under it a thread's
# hand-over costs what its share of the copy saves
_BLOCK_BYTES = 16 << 20
# past eight threads the copy is bound by the host's memory, not by threads
_MAX_POOL_WIDTH = 8

_pool: ThreadPoolExecutor | None = None
_pool_width = 0     # 0: not looked yet; 1: this machine gets no pool
_pool_lock = threading.Lock()


def _gather_pool() -> tuple[ThreadPoolExecutor | None, int]:
    """(the process's gather pool, its width), made on the first call: half
    the cores the process may use, at most ``_MAX_POOL_WIDTH``; (None, 1)
    where that leaves one thread."""
    global _pool, _pool_width
    with _pool_lock:
        if not _pool_width:
            _pool_width = min(_MAX_POOL_WIDTH,
                              max(1, len(os.sched_getaffinity(0)) // 2))
            if _pool_width > 1:
                _pool = ThreadPoolExecutor(_pool_width,
                                           thread_name_prefix="collate.gather")
        return _pool, _pool_width


def _gather(table: np.ndarray, rows: np.ndarray, out: np.ndarray) -> list[Future]:
    """``out[b] = table[rows[b]]``: begun over row blocks on the gather pool
    (-> the blocks' futures, for :func:`_finish`), or, under two blocks' bytes
    or without a pool, done in one call here (-> [])."""
    # mode="clip", not the default "raise": with out= the default gathers
    # into a temporary first and copies it over (PERF.md section 6, PR 24);
    # rows index the table's own rows, never out of range
    pool = None
    if out.nbytes >= 2 * _BLOCK_BYTES:
        pool, width = _gather_pool()
    if pool is None:
        np.take(table, rows, axis=0, out=out, mode="clip")
        return []
    obs.gauge("data.collate.pool_width").set(width)
    row_bytes = out.nbytes // len(rows)
    step = -(-_BLOCK_BYTES // row_bytes)     # rounded up: no block is under
    # a block is rows lo:hi of out: contiguous, so each call writes in place
    return [pool.submit(np.take, table, rows[lo : lo + step], 0,
                        out[lo : lo + step], "clip")
            for lo in range(0, len(rows), step)]


def _finish(blocks: list[Future]) -> None:
    """Wait for every block (none may still be writing when the caller goes
    on, or raises), then raise the first one's error, if any."""
    if blocks:
        wait(blocks)
        obs.counter("data.collate.blocks").inc(len(blocks))
        for block in blocks:
            block.result()


class Batcher:
    def __init__(
        self,
        dataset: CaptionDataset,
        batch_size: int,
        max_len: int,
        mode: str = "caption",
        seq_per_vid: int = 1,
        seed: int = 0,
        drop_last: bool = False,
        host_shard: tuple[int, int] = (0, 1),
    ):
        if mode not in ("caption", "video"):
            raise ValueError(f"unknown mode {mode!r}")
        self.ds = dataset
        self.batch_size = batch_size
        self.max_len = max_len
        self.mode = mode
        self.seq_per_vid = seq_per_vid
        self.seed = seed
        self.epoch_index = 0  # set from the checkpoint epoch on resume
        # divergence-rollback salt (resilience/sentinel.py): 0 keeps the
        # historical (seed, epoch) keying bit-for-bit; a rollback bumps it so
        # the replayed epochs draw a fresh — still deterministic — order
        self.salt = 0
        self.drop_last = drop_last
        # multi-host data feeding (train/multihost.py): every process forms
        # the SAME global batch order — the shuffle is keyed by (seed,
        # epoch_index), no communication needed — and collates only its own
        # contiguous slice of each batch. batch_size stays GLOBAL; collated
        # arrays are [batch_size // count] rows.
        idx, count = host_shard
        if batch_size % count:
            raise ValueError(
                f"global batch_size {batch_size} must be divisible by "
                f"host_shard count {count}"
            )
        if not 0 <= idx < count:
            raise ValueError(f"host_shard index {idx} not in [0, {count})")
        self.host_shard = (idx, count)
        self.local_batch_size = batch_size // count
        self._slot: dict | None = None  # set by epoch() before each _collate

    def _items(self, rng: np.random.Generator | None) -> list[tuple[int, int]]:
        """List of (record_idx, caption_idx) rows for one epoch."""
        items: list[tuple[int, int]] = []
        for ri, rec in enumerate(self.ds.records):
            ncap = max(len(rec.caption_ids), 1)
            if self.mode == "video":
                items.append((ri, 0))
            else:
                k = min(self.seq_per_vid, ncap)
                caps = rng.choice(ncap, size=k, replace=False) if rng is not None else range(k)
                items.extend((ri, int(ci)) for ci in caps)
        if rng is not None:
            rng.shuffle(items)
        return items

    def __iter__(self):
        return self.epoch(shuffle=self.mode == "caption")

    def epoch(self, shuffle: bool = True, staging=None,
              epoch_index: int | None = None, salt: int | None = None):
        """One epoch of batches. ``staging`` (a
        :class:`~cst_captioning_tpu.data.prefetch.StagingRing`) is handed
        over only by a caller that reports every upload back to it (a
        ``PrefetchFeed`` with ``staging=ring``): each batch is then
        collated into the ring's next slot, whose arrays are rewritten a few
        batches later. Without it every batch owns fresh arrays.

        ``epoch_index`` / ``salt``: the shuffle's key, from a caller that
        owns it (the prefetch worker, which draws an epoch while the main
        thread still trains the one before and pins :attr:`epoch_index` for
        it). Neither attribute is then read or written. Left out, the epoch
        is drawn with the batcher's own and :attr:`epoch_index` moves on by
        one: plain iteration, on the thread that owns the attribute."""
        # per-epoch derived RNG: order depends only on (seed, salt, index);
        # unshuffled epochs (eval, template peeks) consume no epoch index
        rng = None
        if shuffle:
            if epoch_index is None:
                epoch_index = self.epoch_index
                self.epoch_index += 1
            if salt is None:
                salt = self.salt
            key = (
                (self.seed, epoch_index) if not salt
                else (self.seed, salt, epoch_index)
            )
            rng = np.random.default_rng(key)
        with obs.span("data.epoch_order"):  # the row list and its shuffle
            items = self._items(rng)
        bs = self.batch_size
        idx, count = self.host_shard
        lb = self.local_batch_size
        n = len(items)
        for start in range(0, n, bs):
            chunk = items[start : start + bs]
            if len(chunk) < bs:
                if self.drop_last:
                    return
                pad = [chunk[i % len(chunk)] for i in range(bs - len(chunk))]
                valid = np.array([True] * len(chunk) + [False] * len(pad))
                chunk = chunk + pad
            else:
                valid = np.ones((bs,), dtype=bool)
            if count > 1:
                # this process's contiguous slice of the global batch
                chunk = chunk[idx * lb : (idx + 1) * lb]
                valid = valid[idx * lb : (idx + 1) * lb]
            # _collate(items, valid) is a seam others replace by name, so its
            # destination rides on the instance, set anew before every call:
            # one thread at a time draws a batcher's epochs (in training the
            # prefetch worker, across epoch ends too)
            self._slot = staging.acquire() if staging is not None else None
            yield self._collate(chunk, valid)

    def _collate(self, items: list[tuple[int, int]], valid: np.ndarray) -> Batch:
        """Rows -> Batch, written into ``self._slot`` (a staging slot's arrays,
        reused) or, without one, into arrays the Batch owns. Same bytes
        either way."""
        # the slot's fence, if any, was waited on before this span began
        slot = self._slot if self._slot is not None else {}
        # one span a batch (never one a row), on the caller's thread: the
        # prefetch worker in training
        with obs.span("data.collate", rows=len(items)):
            bs, T, F = self.local_batch_size, self.max_len, self.ds.max_frames
            dims = {n: store.dim for n, store in self.ds.stores.items()}
            shape = (bs, T, F, tuple(dims.items()))
            if slot.get("shape") == shape:
                obs.counter("data.collate.staged").inc()
            else:
                # np.empty: every element below is written for every batch
                slot.clear()
                slot.update(
                    shape=shape,
                    feats={n: np.empty((bs, F, d), np.float32) for n, d in dims.items()},
                    fmasks={n: np.empty((bs, F), np.float32) for n in dims},
                    labels=np.empty((bs, T), np.int32),
                    mask=np.empty((bs, T), np.float32),
                    weights=np.empty((bs,), np.float32),
                )
                obs.counter("data.collate.fresh").inc()
            feats, fmasks = dict(slot["feats"]), dict(slot["fmasks"])
            labels, mask, weights = slot["labels"], slot["mask"], slot["weights"]

            rows, cis = np.ascontiguousarray(
                np.array(items, np.intp).reshape(-1, 2).T)
            video_ids = [self.ds.video_ids[ri] for ri in rows.tolist()]
            tables = self.ds.feature_tables(rows)
            if tables is not None:
                # one gather a stream, a large one over row blocks on the pool
                blocks = []
                for n, (f, fm) in tables.items():
                    blocks += _gather(f, rows, feats[n])
                    blocks += _gather(fm, rows, fmasks[n])
                _finish(blocks)
            else:
                # memoize per-video h5 reads within the batch: seq_per_vid>1
                # and wrap-padding repeat videos
                read: dict[str, dict] = {}
                for b, vid in enumerate(video_ids):
                    if vid not in read:
                        read[vid] = self.ds.features_for(vid)
                    for n, (f, fm) in read[vid].items():
                        feats[n][b] = f
                        fmasks[n][b] = fm
            # a caption index past a record's last caption reads the last
            lt = self.ds.label_table(T)
            at = lt.first[rows] + np.minimum(cis, lt.ncap[rows] - 1)
            for table, out in ((lt.labels, labels), (lt.mask, mask),
                               (lt.weights, weights)):
                np.take(table, at, axis=0, out=out, mode="clip")
            return Batch(
                feats=feats,
                feat_masks=fmasks,
                labels=labels,
                mask=mask,
                weights=weights,
                valid=valid,
                video_ids=video_ids,
            )

    def num_batches(self) -> int:
        n = len(self._items(None))
        if self.drop_last:
            return n // self.batch_size
        return -(-n // self.batch_size)


class EpochKey(NamedTuple):
    """One shuffled epoch of a :class:`Batcher` as a prefetch feed names it
    (``data/prefetch.py``): what decides its batches, and where the run of
    epochs it belongs to ends. Two keys are equal when the batches staged
    for one are the other's."""

    batcher: Batcher
    salt: int
    index: int      # the shuffle's epoch index
    skip: int       # leading batches left out (a mid-epoch resume)
    until: int      # the run's last epoch has index ``until`` - 1

    def following(self) -> "EpochKey | None":
        """The epoch after this one, whole; None past the run's last."""
        nxt = self.index + 1
        return self._replace(index=nxt, skip=0) if nxt < self.until else None

    def batches(self, staging=None):
        """The epoch's batches, by the key's own index and salt: the
        batcher's attributes are neither read nor moved."""
        return itertools.islice(
            self.batcher.epoch(
                shuffle=True, staging=staging,
                epoch_index=self.index, salt=self.salt,
            ),
            self.skip, None,
        )
