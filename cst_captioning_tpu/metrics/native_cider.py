"""CIDEr-D through the C++ merge-join kernel, for the EVAL scorer.

The RL reward already scores CIDEr-D at ~6.5 µs/row via ``native/creward.cpp``
(flat-array merge joins, parity-pinned against the Python ``metrics.CiderD``
oracle in tests/test_rl.py). The eval path ran the pure-Python scorer — and
a round-5 builder's end-to-end eval run (BASELINE.md, "Eval end-to-end") put
host metric scoring at 71% of the whole config-5 pipeline, with CIDEr/CIDEr-D
the largest single shares. This adapter lets :class:`metrics.scorer.CaptionScorer` route
its CIDEr-D column through the same kernel:

- scoring stays in *string space*: reference and hypothesis words are
  interned into a private id table (ids start above the special tokens, so
  the kernel's PAD/BOS/EOS handling is untouched);
- the reference pools + df are loaded into the kernel ONCE per gts pool
  (the expensive part), so per-epoch validation re-scores at merge-join
  speed — the scorer caches one instance per pool;
- df="corpus" reproduces the Python scorer's eval-mode semantics exactly
  (df over the pools of the ids being scored); a :class:`CorpusDF` is
  forwarded as-is.

Falls back cleanly: :meth:`NativeCiderD.build` returns None when the native
library is unavailable, and :meth:`compute_score` refuses pools it wasn't
prepared for (the caller then uses the Python oracle). Parity with the
Python scorer is pinned in tests/test_metrics_cider.py.
"""

from __future__ import annotations

import ctypes
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from cst_captioning_tpu.config.config import (
    BOS_ID,
    EOS_ID,
    NUM_SPECIAL_TOKENS,
    PAD_ID,
)
from cst_captioning_tpu.metrics.cider import CorpusDF

_SIGMA = 6.0  # CIDEr-D length-penalty sigma (matches metrics.cider / kernel)


class NativeCiderD:
    """Kernel-backed ``CiderD.compute_score`` for one fixed reference pool."""

    def __init__(self, lib, gts: Dict[str, Sequence[Sequence[str]]],
                 df: "CorpusDF | str"):
        self._lib = lib
        self._gts = gts
        self._intern: dict[str, int] = {}

        ids = list(gts.keys())
        if isinstance(df, CorpusDF):
            table, ndoc = df.df, df.num_docs
        else:  # "corpus": df over the pools being scored (eval mode)
            df_obj = CorpusDF.from_refs([gts[i] for i in ids])
            table, ndoc = df_obj.df, df_obj.num_docs
        log_ndoc = math.log(max(float(ndoc), math.e))

        self._handle = lib.crw_create(
            ctypes.c_double(log_ndoc), ctypes.c_double(_SIGMA),
            PAD_ID, BOS_ID, EOS_ID,
        )
        gram_tokens: list[int] = []
        gram_lens: list[int] = []
        gram_counts: list[float] = []
        for gram, count in table.items():
            gram_tokens.extend(self._iid(w) for w in gram)
            gram_lens.append(len(gram))
            gram_counts.append(float(count))
        if gram_lens:
            gt = np.asarray(gram_tokens, np.int32)
            gl = np.asarray(gram_lens, np.int32)
            gc = np.asarray(gram_counts, np.float64)
            lib.crw_set_df(
                self._handle,
                gt.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                gl.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                gc.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
                ctypes.c_int64(len(gram_lens)),
            )
        self._video_index: dict[str, int] = {}
        for vid, pool in gts.items():
            toks = np.asarray(
                [self._iid(w) for ref in pool for w in ref], np.int32
            )
            lens = np.asarray([len(ref) for ref in pool], np.int32)
            idx = lib.crw_add_video(
                self._handle,
                toks.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                lens.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
                ctypes.c_int32(len(pool)),
            )
            self._video_index[vid] = int(idx)

    def _iid(self, word: str) -> int:
        i = self._intern.get(word)
        if i is None:
            i = len(self._intern) + NUM_SPECIAL_TOKENS
            self._intern[word] = i
        return i

    def __del__(self):
        if getattr(self, "_handle", None):
            try:
                self._lib.crw_free(self._handle)
            except Exception:
                pass

    @classmethod
    def build(cls, gts: Dict[str, Sequence[Sequence[str]]],
              df: "CorpusDF | str") -> Optional["NativeCiderD"]:
        """None when the native library can't be loaded/built."""
        from cst_captioning_tpu.native import load_creward

        lib = load_creward()
        if lib is None:
            return None
        return cls(lib, gts, df)

    def covers(self, gts: Dict[str, Sequence[Sequence[str]]]) -> bool:
        """True when this instance was prepared for exactly this pool."""
        return self._gts == gts

    def compute_score(
        self, res: Dict[str, Sequence[Sequence[str]]]
    ) -> Optional[Tuple[float, np.ndarray]]:
        """(corpus mean, per-id array) in res-key order — the Python
        ``CiderD.compute_score`` contract. None when ``res`` ids don't match
        the prepared pool (df="corpus" semantics depend on the id set; the
        caller falls back to the Python oracle).

        Precision contract: the kernel computes per-id scores in double
        but returns them through a float32 ABI (``creward.cpp``'s
        ``out[i] = (float)r``), so results differ from the float64 Python
        oracle by up to ~1e-7 relative (~1e-8 typical). Consumers
        comparing native and fallback paths — best-checkpoint selection
        ties included — must treat scores within that band as equal; the
        band is pinned by the parity tests in tests/test_metrics_cider.py.
        """
        ids = list(res.keys())
        if set(ids) != set(self._video_index):
            return None
        hyps: List[List[str]] = []
        for i in ids:
            assert len(res[i]) == 1, "one hypothesis per id"
            hyps.append(list(res[i][0]))
        width = max((len(h) for h in hyps), default=0) or 1
        rows = np.full((len(ids), width), PAD_ID, np.int32)
        for r, hyp in enumerate(hyps):
            rows[r, : len(hyp)] = [self._iid(w) for w in hyp]
        vidx = np.asarray([self._video_index[i] for i in ids], np.int32)
        out = np.zeros(len(ids), np.float32)
        self._lib.crw_score(
            self._handle,
            vidx.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            np.ascontiguousarray(rows).ctypes.data_as(
                ctypes.POINTER(ctypes.c_int32)
            ),
            ctypes.c_int64(len(ids)),
            ctypes.c_int32(width),
            ctypes.c_double(1.0),   # pure CIDEr-D
            ctypes.c_double(0.0),   # no BLEU term
            ctypes.c_int32(os.cpu_count() or 1),
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        )
        scores = out.astype(np.float64)
        return (float(np.mean(scores)) if len(scores) else 0.0), scores
