"""The benchmark's corpus: seeded synthetic videos in the program's on-disk
schema (one h5 per modality, dataset key = video id, value ``[frames, dim]``
f32; one ``info.json`` with the vocabulary and per-video tokenized captions).

The generator is the benchmark's own (the yardstick may not move when
``cst_captioning_tpu/data/synthetic.py`` does). It keeps that file's model of
a caption corpus — every video has a latent topic, a topic owns a few template
phrases, each reference is a noisy realisation of one template, and the
features are the topic's signature plus noise, so features predict captions
and the consensus reward has structure — and writes only the split a job
reads. A corpus depends on its parameters alone (never on ``--seed``), is
built on the first run of a checkout and reused from ``benchmark/.cache/``.
"""

from __future__ import annotations

import hashlib
import json
import os

import numpy as np

SPECIAL_TOKENS = ("<pad>", "<bos>", "<eos>", "<unk>")   # ids 0..3, the program's


def corpus_key(params: dict) -> str:
    blob = json.dumps(params, sort_keys=True).encode()
    return hashlib.sha256(blob).hexdigest()[:16]


def make_corpus(out_dir: str, *, videos: int, refs_per_video: int,
                caption_len: tuple[int, int], vocab_size: int,
                modalities: dict[str, int], max_frames: int,
                min_frames: int, topics: int = 12,
                templates_per_topic: int = 4, template_noise: float = 0.35,
                feature_noise: float = 0.05, seed: int = 0,
                split: str = "train") -> dict[str, str]:
    """Write the corpus under ``out_dir``; return ``{"info_json": path,
    "<modality>": h5 path, ...}``. ``caption_len`` is inclusive."""
    import h5py

    rng = np.random.default_rng(seed)
    os.makedirs(out_dir, exist_ok=True)
    n_words = vocab_size - len(SPECIAL_TOKENS)
    words = [f"w{i:04d}" for i in range(n_words)]
    pools = np.array_split(np.arange(n_words), topics)
    templates = [
        [rng.choice(pools[t], size=int(rng.integers(caption_len[0],
                                                    caption_len[1] + 1)))
         for _ in range(templates_per_topic)]
        for t in range(topics)
    ]
    topic_of = rng.integers(topics, size=videos)
    n_frames = rng.integers(min_frames, max_frames + 1, size=videos)
    vids = [f"video{i}" for i in range(videos)]

    records = []
    for vi in range(videos):
        t = int(topic_of[vi])
        caps_ids, caps_raw = [], []
        for _ in range(refs_per_video):
            base = templates[t][int(rng.integers(templates_per_topic))]
            noisy = rng.random(base.size) < template_noise
            ids = np.where(noisy, rng.choice(pools[t], size=base.size), base)
            caps_raw.append(" ".join(words[w] for w in ids))
            caps_ids.append([int(w) + len(SPECIAL_TOKENS) for w in ids])
        records.append({"id": vids[vi], "split": split, "topic": t,
                        "captions": caps_raw, "caption_ids": caps_ids})

    paths: dict[str, str] = {}
    for name, dim in modalities.items():
        sig = rng.standard_normal((topics, dim), dtype=np.float32)
        p = os.path.join(out_dir, f"{name}.h5")
        with h5py.File(p + ".tmp", "w") as f:
            for vi in range(videos):
                noise = rng.standard_normal((int(n_frames[vi]), dim),
                                            dtype=np.float32)
                f.create_dataset(
                    vids[vi], data=sig[topic_of[vi]][None, :]
                    + np.float32(feature_noise) * noise)
        os.replace(p + ".tmp", p)
        paths[name] = p
    info = os.path.join(out_dir, "info.json")
    with open(info + ".tmp", "w") as f:
        json.dump({"vocab": list(SPECIAL_TOKENS) + words, "videos": records}, f)
    os.replace(info + ".tmp", info)
    paths["info_json"] = info
    return paths


def ensure_corpus(cache_dir: str, params: dict) -> dict[str, str]:
    """The cached corpus for ``params`` (built now if this checkout has none).
    ``params`` are :func:`make_corpus`'s keyword arguments, as JSON holds
    them."""
    out = os.path.join(cache_dir, "corpus-" + corpus_key(params))
    paths = {name: os.path.join(out, f"{name}.h5")
             for name in params["modalities"]}
    paths["info_json"] = os.path.join(out, "info.json")
    # info.json is written last: its presence says the corpus is whole
    if not os.path.exists(paths["info_json"]):
        kw = dict(params)
        kw["caption_len"] = tuple(kw["caption_len"])
        make_corpus(out, **kw)
    return paths
