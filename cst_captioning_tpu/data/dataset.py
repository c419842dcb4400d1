"""Caption dataset: h5 multi-modality features + json metadata.

Mirrors the reference's on-disk contract (SURVEY.md §3.4) with a cleaner
schema we own (the reference's exact h5 key names were unverifiable, §0):

- one h5 file per modality; dataset key = video id; value = [n_frames, dim]
  float array (mean-pooled modalities may have n_frames == 1),
- one ``info.json``: vocab table, per-video split + tokenized captions
  (both as id lists and raw strings, the latter feeding reward/eval pools),
- optional ``consensus_weights`` npz (WXE) and CIDEr df pickle-free npz (RL),
  produced by :mod:`cst_captioning_tpu.data.preprocess`.

All feature arrays are padded/truncated to ``max_frames`` on read and carry a
frame-validity mask, so every batch has static shapes for XLA.

What a dataset holds in host memory beside its records: with
``cache_features`` one contiguous feature table a stream (n_videos *
max_frames * sum(dims) * 4 bytes, filled a row at a time as rows are first
asked for), and, for every ``max_len`` a batcher has asked for, the label
table (:meth:`CaptionDataset.label_table`: n_captions * (max_len * 8 + 4)
bytes, 31 MB for MSR-VTT's 130 k captions at 30, built whole on first use by
the thread that asks). The dataset owns both; what it hands out is read-only.
"""

from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import NamedTuple

import numpy as np

from cst_captioning_tpu.config.config import EOS_ID, PAD_ID
from cst_captioning_tpu.data.vocab import Vocab

try:
    import h5py
except ImportError:  # pragma: no cover - h5py is baked into the image
    h5py = None


@dataclass
class VideoRecord:
    video_id: str
    split: str
    # tokenized captions as id lists (no BOS/EOS; added at batch time)
    caption_ids: list[list[int]] = field(default_factory=list)
    # raw tokenized caption strings (reward/eval reference pools)
    captions: list[str] = field(default_factory=list)
    # per-caption consensus weights (WXE), parallel to caption_ids
    weights: list[float] = field(default_factory=list)


class FeatureStore:
    """Lazy h5-backed frame features for one modality, padded to max_frames."""

    def __init__(self, path: str, max_frames: int, dim: int | None = None):
        if h5py is None:
            raise RuntimeError("h5py unavailable")
        self.path = path
        self.max_frames = max_frames
        self._h5 = h5py.File(path, "r")
        first = next(iter(self._h5))
        arr = self._h5[first]
        self.dim = int(dim if dim is not None else arr.shape[-1])

    def keys(self):
        return list(self._h5.keys())

    def get(self, video_id: str) -> tuple[np.ndarray, np.ndarray]:
        """-> (feats [max_frames, dim] f32, mask [max_frames] f32)."""
        raw = np.asarray(self._h5[video_id], dtype=np.float32)
        if raw.ndim == 1:
            raw = raw[None, :]
        n = min(raw.shape[0], self.max_frames)
        if raw.shape[0] > self.max_frames:
            # uniform temporal subsample instead of truncation: keeps coverage
            # of the whole clip when frame counts exceed the budget.
            idx = np.linspace(0, raw.shape[0] - 1, self.max_frames).round().astype(int)
            raw = raw[idx]
            n = self.max_frames
        feats = np.zeros((self.max_frames, self.dim), dtype=np.float32)
        feats[:n] = raw[:n]
        mask = np.zeros((self.max_frames,), dtype=np.float32)
        mask[:n] = 1.0
        return feats, mask

    def close(self):
        self._h5.close()


def _read_only(a: np.ndarray) -> np.ndarray:
    v = a.view()
    v.flags.writeable = False
    return v


def encode_label_row(caption_ids: list[int], max_len: int) -> tuple[np.ndarray, np.ndarray]:
    """ids (no specials) -> (labels [T], mask [T]) with EOS and PAD=0 padding."""
    row = np.full((max_len,), PAD_ID, dtype=np.int32)
    m = np.zeros((max_len,), dtype=np.float32)
    toks = caption_ids[: max_len - 1]          # reserve one slot for EOS
    row[: len(toks)] = toks
    row[len(toks)] = EOS_ID
    m[: len(toks) + 1] = 1.0
    return row, m


class LabelTable(NamedTuple):
    """Every caption of every record as a batch row, read-only: the row of
    caption ``ci`` of record ``ri`` is ``first[ri] + min(ci, ncap[ri] - 1)``."""

    labels: np.ndarray      # [C, T] int32: word ids + EOS, then PAD
    mask: np.ndarray        # [C, T] float32: 1 on real tokens incl. EOS
    weights: np.ndarray     # [C]    float32: WXE consensus weights
    first: np.ndarray       # [n_videos] intp: a record's first row
    ncap: np.ndarray        # [n_videos] intp: its rows (at least one)


class CaptionDataset:
    """Videos of one split with their features, captions, and reward pools."""

    def __init__(
        self,
        info_json: str,
        feature_files: dict[str, str],
        split: str,
        max_frames: int = 60,
        consensus_weights: str = "",
        cache_features: bool = False,
    ):
        with open(info_json) as f:
            info = json.load(f)
        self.vocab = Vocab(info["vocab"])
        self.split = split
        self.records: list[VideoRecord] = []
        for v in info["videos"]:
            if v["split"] != split:
                continue
            if not v["caption_ids"]:
                raise ValueError(
                    f"video {v['id']!r} has no captions; every record needs at "
                    "least one (empty rows would produce all-PAD label rows)"
                )
            self.records.append(
                VideoRecord(
                    video_id=v["id"],
                    split=v["split"],
                    caption_ids=[list(map(int, c)) for c in v["caption_ids"]],
                    captions=[str(c) for c in v["captions"]],
                )
            )
        if not self.records:
            raise ValueError(f"no videos for split {split!r} in {info_json}")
        self.stores = {
            name: FeatureStore(path, max_frames=max_frames)
            for name, path in feature_files.items()
        }
        self.max_frames = max_frames
        self.video_ids = [r.video_id for r in self.records]  # by record index
        self._gts_pool: dict[str, list[str]] | None = None
        self._label_tables: dict[int, LabelTable] = {}  # by max_len
        # opt-in host-RAM feature cache (DataConfig.cache_features): h5 reads
        # are the host hot path on repeat epochs — with the cache, each
        # video's padded features are read once, into its row of one
        # contiguous table a stream, and every later batch is one gather a
        # stream (Batcher._collate); the module docstring has its memory
        self._tables: dict[str, tuple[np.ndarray, np.ndarray]] | None = None
        if cache_features:
            n = len(self.records)
            self._tables = {
                name: (np.zeros((n, max_frames, store.dim), np.float32),
                       np.zeros((n, max_frames), np.float32))
                for name, store in self.stores.items()
            }
            # what is handed out: the same memory, read-only — an in-place
            # consumer would silently poison later epochs; make that an
            # immediate ValueError instead
            self._tables_ro = {
                name: (_read_only(f), _read_only(m))
                for name, (f, m) in self._tables.items()
            }
            self._filled = np.zeros((n,), dtype=bool)
            self._row = {r.video_id: i for i, r in enumerate(self.records)}
        if consensus_weights:
            if not os.path.exists(consensus_weights):
                raise FileNotFoundError(
                    f"consensus_weights file not found: {consensus_weights}"
                )
            self._load_weights(consensus_weights)
        else:
            for r in self.records:
                r.weights = [1.0] * len(r.caption_ids)

    def _load_weights(self, path: str):
        """npz: one array per video id, parallel to its caption list."""
        data = np.load(path)
        for r in self.records:
            if r.video_id in data:
                w = np.asarray(data[r.video_id], dtype=np.float32)
                if len(w) != len(r.caption_ids):
                    raise ValueError(
                        f"weights/captions length mismatch for {r.video_id}"
                    )
                r.weights = [float(x) for x in w]
            else:
                r.weights = [1.0] * len(r.caption_ids)

    def __len__(self) -> int:
        return len(self.records)

    def feature_tables(
        self, record_indices: np.ndarray
    ) -> dict[str, tuple[np.ndarray, np.ndarray]] | None:
        """name -> (feats [n_videos, max_frames, dim], masks [n_videos,
        max_frames]), read-only and indexed by record index, with the rows of
        ``record_indices`` filled (from h5 on a video's first read); None
        without ``cache_features``. Rows nobody asked for yet hold zeros."""
        if self._tables is None:
            return None
        todo = record_indices[~self._filled[record_indices]]
        for ri in np.unique(todo):
            vid = self.records[ri].video_id
            for name, store in self.stores.items():
                feats, masks = self._tables[name]
                feats[ri], masks[ri] = store.get(vid)
            self._filled[ri] = True
        return self._tables_ro

    def features_for(self, video_id: str) -> dict[str, tuple[np.ndarray, np.ndarray]]:
        if self._tables is not None:
            ri = self._row[video_id]
            tables = self.feature_tables(np.array([ri]))
            return {name: (f[ri], m[ri]) for name, (f, m) in tables.items()}
        return {name: store.get(video_id) for name, store in self.stores.items()}

    def label_table(self, max_len: int) -> LabelTable:
        """What :func:`encode_label_row` gives for every caption of every
        record, with the captions' consensus weights, built on the first call
        for a ``max_len`` and kept (records are immutable post-init). A
        record without captions holds one all-PAD row of weight 1. Two
        threads that ask at once both build it, to the same bytes."""
        table = self._label_tables.get(max_len)
        if table is None:
            ncap = np.array([max(len(r.caption_ids), 1) for r in self.records],
                            np.intp)
            first = np.cumsum(ncap) - ncap
            labels = np.full((int(ncap.sum()), max_len), PAD_ID, np.int32)
            mask = np.zeros(labels.shape, np.float32)
            weights = np.ones(labels.shape[:1], np.float32)
            for rec, k in zip(self.records, first.tolist()):
                for ci, ids in enumerate(rec.caption_ids):
                    labels[k + ci], mask[k + ci] = encode_label_row(ids, max_len)
                if rec.weights:
                    weights[k : k + len(rec.weights)] = rec.weights
            table = self._label_tables[max_len] = LabelTable(
                *map(_read_only, (labels, mask, weights, first, ncap)))
        return table

    def gts_pool(self) -> dict[str, list[str]]:
        """video_id -> list of tokenized GT caption strings (reward/eval refs).

        Cached after the first call (records are immutable post-init); callers
        treat the returned pool as read-only.
        """
        if self._gts_pool is None:
            self._gts_pool = {r.video_id: list(r.captions) for r in self.records}
        return self._gts_pool

    def close(self):
        for s in self.stores.values():
            s.close()
