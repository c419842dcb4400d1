"""Deterministic synthetic dataset generator (test/bench fixtures).

The real MSVD/MSR-VTT h5s are not shippable (SURVEY.md §4 item 5), so tests
and benchmarks run on seeded synthetic data with the exact on-disk schema of
:mod:`cst_captioning_tpu.data.dataset`: per-modality h5 feature files plus an
``info.json``.

Captions are topic-conditioned: each video draws a latent topic, its captions
are built from that topic's word pool, and its features embed the topic
pattern plus gaussian noise — so features genuinely predict captions and
overfit/learning tests (SURVEY.md §4 item 3) are meaningful, not vacuous.

Two caption styles:

- ``"pool"`` (default, the original): every caption is an i.i.d. random word
  sequence from the topic pool. Good for overfit/mechanics tests, but the GT
  pool has NO consensus structure — there is nothing for consensus-reward
  (CST) training to sharpen that transfers across videos, so XE-vs-CST
  comparisons on this style measure memorization, not the algorithm.
- ``"template"``: each topic owns a few canonical phrases; every caption is
  a noisy realization of one of them (word-level replacement noise). This
  mirrors real caption pools — many roughly-agreeing captions around a few
  central phrasings — so the consensus reward points at structure that
  GENERALIZES to held-out videos of the same topic. Use for any XE-vs-CST
  quality comparison (chip_smoke.py does).
"""

from __future__ import annotations

import json
import os

import numpy as np

from cst_captioning_tpu.data.vocab import Vocab

try:
    import h5py
except ImportError:  # pragma: no cover
    h5py = None


def make_synthetic_dataset(
    out_dir: str,
    num_videos: int = 24,
    num_topics: int = 4,
    vocab_words: int = 40,
    captions_per_video: int = 5,
    caption_len: tuple[int, int] = (4, 9),
    modalities: dict[str, int] | None = None,
    max_frames: int = 8,
    splits: tuple[float, float] = (0.75, 0.125),   # train, val (rest = test)
    seed: int = 0,
    caption_style: str = "pool",     # "pool" | "template" (see module doc)
    templates_per_topic: int = 4,
    template_noise: float = 0.25,    # per-word replacement probability
    feature_noise: float = 0.3,      # per-frame gaussian amplitude on top of
                                     # the topic signature. NOTE: this is a
                                     # stable per-video fingerprint (frame
                                     # means identify the video), so models
                                     # CAN memorize per-video targets through
                                     # it; pass ~0.05 for generalization
                                     # studies where that channel must be
                                     # closed
) -> dict[str, str]:
    """Writes h5 + info.json under ``out_dir``; returns the path map.

    Returns ``{"info_json": ..., "<modality>": <h5 path>, ...}``.
    """
    if h5py is None:
        raise RuntimeError("h5py unavailable")
    modalities = modalities or {"resnet": 64}
    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)

    if caption_style not in ("pool", "template"):
        raise ValueError(f"unknown caption_style {caption_style!r}")
    words = [f"w{i:03d}" for i in range(vocab_words)]
    vocab = Vocab.from_corpus_words(words)
    # topic -> disjoint word pool
    pools = np.array_split(np.arange(vocab_words), num_topics)
    # "template" style: per-topic canonical phrases shared by ALL videos of
    # the topic (train and held-out alike) — the consensus target
    topic_templates: list[list[np.ndarray]] = []
    if caption_style == "template":
        for t in range(num_topics):
            topic_templates.append([
                rng.choice(pools[t],
                           size=int(rng.integers(caption_len[0], caption_len[1])),
                           replace=True)
                for _ in range(templates_per_topic)
            ])

    # topic signature per modality: a fixed random pattern features orbit
    sigs = {
        name: rng.normal(size=(num_topics, dim)).astype(np.float32)
        for name, dim in modalities.items()
    }

    videos = []
    feat_arrays: dict[str, dict[str, np.ndarray]] = {m: {} for m in modalities}
    n_train = int(num_videos * splits[0])
    n_val = int(num_videos * splits[1])
    for vi in range(num_videos):
        vid = f"video{vi}"
        split = "train" if vi < n_train else ("val" if vi < n_train + n_val else "test")
        topic = int(rng.integers(num_topics))
        caps_ids, caps_raw = [], []
        for _ in range(captions_per_video):
            pool = pools[topic]
            if caption_style == "template":
                base = topic_templates[topic][
                    int(rng.integers(templates_per_topic))
                ]
                noise = rng.random(base.size) < template_noise
                word_ids = np.where(
                    noise, rng.choice(pool, size=base.size, replace=True), base
                )
            else:
                L = int(rng.integers(caption_len[0], caption_len[1]))
                word_ids = rng.choice(pool, size=L, replace=True)
            toks = [words[w] for w in word_ids]
            caps_raw.append(" ".join(toks))
            caps_ids.append(vocab.encode(toks))
        videos.append(
            {
                "id": vid,
                "split": split,
                "topic": topic,
                "captions": caps_raw,
                "caption_ids": caps_ids,
            }
        )
        n_frames = int(rng.integers(max(2, max_frames // 2), max_frames + 1))
        for name, dim in modalities.items():
            noise = feature_noise * rng.normal(size=(n_frames, dim)).astype(
                np.float32
            )
            feat_arrays[name][vid] = sigs[name][topic][None, :] + noise

    paths: dict[str, str] = {}
    for name in modalities:
        p = os.path.join(out_dir, f"{name}.h5")
        with h5py.File(p, "w") as f:
            for vid, arr in feat_arrays[name].items():
                f.create_dataset(vid, data=arr)
        paths[name] = p

    info = {"vocab": vocab.words, "videos": videos}
    info_path = os.path.join(out_dir, "info.json")
    with open(info_path, "w") as f:
        json.dump(info, f)
    paths["info_json"] = info_path
    return paths
