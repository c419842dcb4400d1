"""Data layer tests: vocab, synthetic fixtures, dataset, batcher, preprocess."""

import threading
import time

import numpy as np
import pytest

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID, UNK_ID
from cst_captioning_tpu.data import (
    Batcher,
    CaptionDataset,
    Vocab,
    build_vocab,
    compute_cider_df,
    compute_consensus_weights,
    make_synthetic_dataset,
    tokenize_captions,
)


@pytest.fixture(scope="module")
def synth(tmp_path_factory):
    out = tmp_path_factory.mktemp("synth")
    paths = make_synthetic_dataset(
        str(out),
        num_videos=16,
        modalities={"resnet": 32, "c3d": 16},
        max_frames=6,
        seed=7,
    )
    return paths


def test_vocab_roundtrip():
    v = Vocab.from_corpus_words(["cat", "dog", "runs"])
    assert len(v) == 7
    ids = v.encode(["dog", "runs", "zebra"])
    assert ids == [v.encode(["dog"])[0], v.encode(["runs"])[0], UNK_ID]
    assert v.decode([BOS_ID] + v.encode(["cat", "runs"]) + [EOS_ID, PAD_ID]) == "cat runs"
    v2 = Vocab.from_json(v.to_json())
    assert v2.words == v.words


def test_synthetic_dataset_loads(synth):
    ds = CaptionDataset(
        synth["info_json"],
        {"resnet": synth["resnet"], "c3d": synth["c3d"]},
        split="train",
        max_frames=6,
    )
    assert len(ds) == 12  # 16 * 0.75
    feats = ds.features_for(ds.records[0].video_id)
    f, m = feats["resnet"]
    assert f.shape == (6, 32) and m.shape == (6,)
    assert m.sum() >= 2
    # masked-out frames are zero
    assert np.all(f[m == 0] == 0)
    pool = ds.gts_pool()
    assert all(len(caps) == 5 for caps in pool.values())
    ds.close()


def test_batcher_caption_mode_shapes(synth):
    ds = CaptionDataset(synth["info_json"], {"resnet": synth["resnet"]}, "train", 6)
    b = Batcher(ds, batch_size=5, max_len=12, mode="caption", seq_per_vid=2, seed=1)
    batches = list(b.epoch())
    assert len(batches) == b.num_batches()
    for batch in batches:
        assert batch.labels.shape == (5, 12)
        assert batch.mask.shape == (5, 12)
        assert batch.feats["resnet"].shape == (5, 6, 32)
        # every valid row ends with EOS at the last masked position
        for r in range(5):
            n = int(batch.mask[r].sum())
            assert n >= 1
            assert batch.labels[r, n - 1] == EOS_ID
            assert np.all(batch.labels[r, n:] == PAD_ID)
    # wrap-padding marks invalid rows
    total_valid = sum(b2.size for b2 in batches)
    assert total_valid == 12 * 2
    ds.close()


def test_batcher_video_mode_unique_ids(synth):
    ds = CaptionDataset(synth["info_json"], {"resnet": synth["resnet"]}, "test", 6)
    b = Batcher(ds, batch_size=3, max_len=12, mode="video")
    seen = []
    for batch in b.epoch(shuffle=False):
        seen.extend(v for v, ok in zip(batch.video_ids, batch.valid) if ok)
    assert sorted(seen) == sorted(r.video_id for r in ds.records)
    ds.close()


def test_preprocess_consensus_weights():
    raw = {
        "v1": ["a cat runs fast", "a cat runs", "a dog sleeps here now"],
        "v2": ["the sun is bright", "the sun is very bright"],
    }
    tok = tokenize_captions(raw)
    v = build_vocab(tok, min_count=1)
    assert "<unk>" in v.words and "cat" in v.words
    w = compute_consensus_weights(tok)
    assert set(w) == {"v1", "v2"}
    # the outlier caption ("a dog sleeps...") gets the lowest consensus weight
    assert np.argmin(w["v1"]) == 2
    # mean-1 normalization per video
    for arr in w.values():
        assert arr.mean() == pytest.approx(1.0, abs=1e-5)
    df = compute_cider_df(tok)
    assert df.num_docs == 2
    assert df.df  # non-empty


def test_prefetch_to_device(synth):
    import jax

    from cst_captioning_tpu.data.prefetch import prefetch_to_device

    ds = CaptionDataset(synth["info_json"], {"resnet": synth["resnet"]}, "train", 6)
    b = Batcher(ds, batch_size=4, max_len=10, mode="caption")
    out = list(
        prefetch_to_device(
            b.epoch(shuffle=False),
            size=2,
            transform=lambda batch: {"labels": batch.labels, "mask": batch.mask},
        )
    )
    assert len(out) == b.num_batches()
    assert isinstance(out[0]["labels"], jax.Array)
    ds.close()


def test_prefetch_propagates_errors():
    from cst_captioning_tpu.data.prefetch import prefetch_to_device

    def bad_iter():
        yield np.zeros(3)
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError, match="boom"):
        list(prefetch_to_device(bad_iter(), size=2))


def test_prefetch_early_abandon_does_not_leak_worker():

    from cst_captioning_tpu.data.prefetch import prefetch_to_device

    n_before = threading.active_count()

    def src():
        for i in range(100):
            yield np.full((2,), i)

    it = prefetch_to_device(src(), size=2)
    next(it)
    it.close()  # abandon early -> generator finally must retire the worker
    # worker must exit promptly rather than blocking on a full queue
    for _ in range(50):
        if threading.active_count() <= n_before:
            break
        time.sleep(0.05)
    assert threading.active_count() <= n_before


def test_dataset_rejects_missing_weights_and_empty_captions(synth, tmp_path):
    import json

    with pytest.raises(FileNotFoundError):
        CaptionDataset(
            synth["info_json"],
            {"resnet": synth["resnet"]},
            "train",
            6,
            consensus_weights=str(tmp_path / "nope.npz"),
        )
    with open(synth["info_json"]) as f:
        info = json.load(f)
    info["videos"][0]["caption_ids"] = []
    bad = tmp_path / "bad_info.json"
    bad.write_text(json.dumps(info))
    with pytest.raises(ValueError, match="no captions"):
        CaptionDataset(str(bad), {"resnet": synth["resnet"]}, "train", 6)


def test_synthetic_template_style(tmp_path):
    """caption_style="template": same-topic videos share consensus n-gram
    structure (noisy realizations of the topic's canonical phrases) while
    different topics share none — the precondition an XE-vs-CST quality
    comparison rests on. feature_noise scales the per-video
    fingerprint amplitude."""
    import collections
    import json as _json

    paths = make_synthetic_dataset(
        str(tmp_path),
        num_videos=24,
        num_topics=2,
        vocab_words=80,
        captions_per_video=10,
        caption_len=(5, 9),
        modalities={"resnet": 16},
        max_frames=4,
        seed=11,
        caption_style="template",
        template_noise=0.2,
        feature_noise=0.01,
    )
    info = _json.load(open(paths["info_json"]))
    by_topic = collections.defaultdict(list)
    for v in info["videos"]:
        by_topic[v["topic"]].append(v)

    def bigrams(video):
        s = set()
        for c in video["captions"]:
            w = c.split()
            s |= set(zip(w, w[1:]))
        return s

    t0, t1 = by_topic[0], by_topic[1]
    same = bigrams(t0[0]) & bigrams(t0[1])
    cross = bigrams(t0[0]) & bigrams(t1[0])
    assert len(same) > 3       # consensus transfers across same-topic videos
    assert len(cross) == 0     # disjoint word pools -> no cross-topic overlap

    # low feature_noise: same-topic features nearly identical frame-to-frame
    import h5py

    with h5py.File(paths["resnet"], "r") as f:
        a = np.asarray(f[t0[0]["id"]])
        b = np.asarray(f[t0[1]["id"]])
        x = np.asarray(f[t1[0]["id"]])
    assert np.abs(a.mean(0) - b.mean(0)).max() < 0.1     # same topic: close
    assert np.abs(a.mean(0) - x.mean(0)).max() > 0.5     # cross topic: far

    with pytest.raises(ValueError, match="caption_style"):
        make_synthetic_dataset(str(tmp_path / "bad"), caption_style="nope")


def test_feature_cache_serves_without_h5(synth):
    """cache_features=True: after a warm pass, features come from host RAM —
    identical to the uncached reads, and served even once the h5 stores are
    closed (proving repeat epochs do zero h5 IO)."""
    cold = CaptionDataset(
        synth["info_json"], {"resnet": synth["resnet"]}, "train", 6
    )
    warm = CaptionDataset(
        synth["info_json"], {"resnet": synth["resnet"]}, "train", 6,
        cache_features=True,
    )
    ids = [r.video_id for r in warm.records]
    baseline = {v: cold.features_for(v) for v in ids}
    for v in ids:
        warm.features_for(v)
    for s in warm.stores.values():
        s.close()                      # h5 gone; cache must stand alone
    for v in ids:
        f, m = warm.features_for(v)["resnet"]
        np.testing.assert_array_equal(f, baseline[v]["resnet"][0])
        np.testing.assert_array_equal(m, baseline[v]["resnet"][1])
    cold.close()


# ---- one gather a stream into reused, fenced staging slots -------------------

_BOTH = {"resnet": 32, "c3d": 16}


def _open(synth, cache, split="train"):
    return CaptionDataset(
        synth["info_json"], {n: synth[n] for n in _BOTH}, split, 6,
        cache_features=cache,
    )


def _reference_epoch(ds, batch_size, max_len, mode, seq_per_vid, seed, salt,
                     epoch_index, host_shard):
    """The batches of one shuffled epoch by the plainest possible row loop:
    what `Batcher` must produce, byte for byte, wherever it writes them."""
    key = (seed, epoch_index) if not salt else (seed, salt, epoch_index)
    rng = np.random.default_rng(key)
    items = []
    for ri, rec in enumerate(ds.records):
        if mode == "video":
            items.append((ri, 0))
        else:
            k = min(seq_per_vid, len(rec.caption_ids))
            picks = rng.choice(len(rec.caption_ids), size=k, replace=False)
            items.extend((ri, int(ci)) for ci in picks)
    rng.shuffle(items)
    idx, count = host_shard
    lb = batch_size // count
    out = []
    for start in range(0, len(items), batch_size):
        chunk = items[start:start + batch_size]
        n_real = len(chunk)
        chunk = chunk + [chunk[i % n_real] for i in range(batch_size - n_real)]
        valid = np.arange(batch_size) < n_real
        chunk, valid = chunk[idx * lb:(idx + 1) * lb], valid[idx * lb:(idx + 1) * lb]
        ref = {
            "labels": np.zeros((lb, max_len), np.int32),
            "mask": np.zeros((lb, max_len), np.float32),
            "weights": np.ones((lb,), np.float32),
            "valid": valid,
            "video_ids": [ds.records[ri].video_id for ri, _ in chunk],
        }
        for name, store in ds.stores.items():
            got = [store.get(v) for v in ref["video_ids"]]
            ref["feats." + name] = np.stack([f for f, _ in got])
            ref["feat_masks." + name] = np.stack([m for _, m in got])
        for b, (ri, ci) in enumerate(chunk):
            toks = ds.records[ri].caption_ids[ci][:max_len - 1]
            ref["labels"][b, :len(toks)] = toks
            ref["labels"][b, len(toks)] = EOS_ID
            ref["mask"][b, :len(toks) + 1] = 1.0
            ref["weights"][b] = ds.records[ri].weights[ci]
        out.append(ref)
    return out


def _flat(batch):
    out = {"labels": batch.labels, "mask": batch.mask, "weights": batch.weights,
           "valid": batch.valid, "video_ids": batch.video_ids}
    for name in batch.feats:
        out["feats." + name] = batch.feats[name]
        out["feat_masks." + name] = batch.feat_masks[name]
    return out


def _assert_same(got: dict, ref: dict):
    assert set(got) == set(ref)
    for k, want in ref.items():
        if k == "video_ids":
            assert list(got[k]) == want
            continue
        have = np.asarray(got[k])
        assert have.dtype == want.dtype and have.shape == want.shape, k
        np.testing.assert_array_equal(have, want, err_msg=k)


def _blocks():
    from cst_captioning_tpu import obs

    return obs.counter("data.collate.blocks").snapshot()


@pytest.mark.parametrize("pooled", [False, True], ids=["inline", "pooled"])
@pytest.mark.parametrize("staged", [False, True], ids=["fresh", "staged"])
@pytest.mark.parametrize("cache", [False, True], ids=["h5", "table"])
@pytest.mark.parametrize(
    "mode,seq_per_vid,host_shard",
    [("video", 1, (0, 1)), ("caption", 2, (0, 1)), ("caption", 3, (1, 2)),
     ("video", 1, (0, 2))],
    ids=["video", "caption_spv2", "caption_spv3_shard1of2", "video_shard0of2"],
)
def test_batches_equal_row_loop_reference(synth, mode, seq_per_vid, host_shard,
                                          cache, staged, pooled, request):
    """Two epochs (so the table is read cold and warm, and every slot is
    rewritten), a wrap-padded last batch, a salt: each array of each batch
    is the reference's, whether collated into fresh arrays or a ring, by one
    gather a stream on this thread or by row blocks on the pool."""
    from cst_captioning_tpu.data.prefetch import StagingRing

    if pooled:
        request.getfixturevalue("small_blocks")
    blocks0 = _blocks()
    ds, plain = _open(synth, cache), _open(synth, False)
    kw = dict(batch_size=10, max_len=7, mode=mode, seq_per_vid=seq_per_vid,
              seed=5)
    batcher = Batcher(ds, host_shard=host_shard, **kw)
    batcher.salt = 3
    ring = StagingRing(1) if staged else None
    for epoch_index in (0, 1):
        refs = _reference_epoch(plain, salt=3, epoch_index=epoch_index,
                                host_shard=host_shard, **kw)
        assert len(refs) == batcher.num_batches()
        assert 0 < refs[-1]["valid"].sum() < len(refs[-1]["valid"])
        n = 0
        # compared one at a time: a staged Batch is the ring's after the next
        for batch, ref in zip(batcher.epoch(staging=ring), refs, strict=True):
            _assert_same(_flat(batch), ref)
            n += 1
        assert n == len(refs)
    # a batch of 10 rows: a block a resnet row, one for two c3d rows; 5 rows
    # a host: 5 and 3. The h5 path and a default-sized block run none
    per_batch = {10: 10 + 5, 5: 5 + 3}[batcher.local_batch_size]
    want = 2 * len(refs) * per_batch if pooled and cache else 0
    assert _blocks() - blocks0 == want
    ds.close()
    plain.close()


def test_label_table_equals_encode_label_row(synth, tmp_path):
    """Every caption of every record, at a max_len some captions outgrow,
    under consensus weights other than 1; a caption index past a record's
    last reads the last one; the table is read-only and built once."""
    from cst_captioning_tpu.data.dataset import encode_label_row

    base = _open(synth, False)
    rng = np.random.default_rng(3)
    w = {r.video_id: rng.uniform(0.25, 2.0, len(r.caption_ids)).astype(np.float32)
         for r in base.records}
    np.savez(tmp_path / "w.npz", **w)
    base.close()
    ds = CaptionDataset(
        synth["info_json"], {n: synth[n] for n in _BOTH}, "train", 6,
        consensus_weights=str(tmp_path / "w.npz"),
    )
    T = 7          # captions hold 4 to 8 words: under, at and over T - 1
    assert any(len(c) > T - 1 for r in ds.records for c in r.caption_ids)
    assert any(len(c) < T - 1 for r in ds.records for c in r.caption_ids)
    lt = ds.label_table(T)
    assert lt is ds.label_table(T) and lt is not ds.label_table(T + 1)
    assert lt.labels.dtype == np.int32 and lt.mask.dtype == np.float32
    assert lt.weights.dtype == np.float32
    assert len(lt.labels) == sum(len(r.caption_ids) for r in ds.records)
    with pytest.raises(ValueError):
        lt.labels[0, 0] = 1
    items = []
    for ri, rec in enumerate(ds.records):
        assert lt.ncap[ri] == len(rec.caption_ids)
        for ci, ids in enumerate(rec.caption_ids):
            row, m = encode_label_row(ids, T)
            k = lt.first[ri] + ci
            np.testing.assert_array_equal(lt.labels[k], row)
            np.testing.assert_array_equal(lt.mask[k], m)
            assert lt.weights[k] == np.float32(rec.weights[ci]) != 1.0
            items.append((ri, ci))
        items.append((ri, len(rec.caption_ids) + 2))    # past the last
    # and through a batch, on the h5 path
    batcher = Batcher(ds, batch_size=len(items), max_len=T, mode="caption")
    batcher._slot = None
    got = batcher._collate(items, np.ones((len(items),), bool))
    for b, (ri, ci) in enumerate(items):
        rec = ds.records[ri]
        ci = min(ci, len(rec.caption_ids) - 1)
        row, m = encode_label_row(rec.caption_ids[ci], T)
        np.testing.assert_array_equal(got.labels[b], row)
        np.testing.assert_array_equal(got.mask[b], m)
        assert got.weights[b] == np.float32(rec.weights[ci])
        assert got.video_ids[b] == rec.video_id
    ds.close()


def test_feature_table_is_read_only_and_lazy(synth):
    ds, plain = _open(synth, True), _open(synth, False)
    vid = ds.records[3].video_id
    tables = ds.feature_tables(np.array([3, 3]))
    f, m = tables["resnet"]
    assert f.shape == (len(ds), 6, 32) and m.shape == (len(ds), 6)
    np.testing.assert_array_equal(f[3], plain.features_for(vid)["resnet"][0])
    assert not f[0].any()                  # nobody asked for row 0 yet
    with pytest.raises(ValueError):
        f[3, 0, 0] = 1.0
    with pytest.raises(ValueError):
        ds.features_for(vid)["c3d"][1][0] = 0.0
    assert plain.feature_tables(np.array([3])) is None
    ds.close()
    plain.close()


@pytest.mark.parametrize("size", [2, 0], ids=["worker", "inline"])
def test_staged_epoch_through_prefetch_keeps_every_item(synth, size):
    """The Trainer's wiring on the CPU backend, keeping every yielded item
    till after the epochs end: none was rewritten under an upload that was
    unfinished or that took the slot's memory over."""
    import jax

    from cst_captioning_tpu.data.prefetch import StagingRing, prefetch_to_device

    ds, plain = _open(synth, True), _open(synth, False)
    kw = dict(batch_size=4, max_len=7, mode="caption", seq_per_vid=3, seed=2)
    batcher = Batcher(ds, **kw)
    ring = StagingRing(size)
    kept, refs = [], []
    for epoch_index in (0, 1):
        refs += _reference_epoch(plain, salt=0, epoch_index=epoch_index,
                                 host_shard=(0, 1), **kw)
        kept += list(prefetch_to_device(
            batcher.epoch(staging=ring), size=size, staging=ring,
            transform=lambda b: {k: v for k, v in _flat(b).items()
                                 if k != "video_ids"},
        ))
    assert len(kept) == len(refs) > 2 * (size + 2)
    assert isinstance(kept[0]["feats.resnet"], jax.Array)
    for got, ref in zip(kept, refs, strict=True):
        del ref["video_ids"]
        _assert_same(jax.device_get(got), ref)
    ds.close()
    plain.close()


class _SlowUpload:
    """What a placement returns while the transfer is still reading the host
    buffer: ready when released."""

    def __init__(self, snapshot):
        self.snapshot = snapshot
        self.done = threading.Event()
        self.waited = False

    def block_until_ready(self):
        self.waited = True
        assert self.done.wait(10.0)
        return self


def _wait_for(cond, seconds=5.0):
    t0 = time.monotonic()
    while not cond():
        assert time.monotonic() - t0 < seconds
        time.sleep(0.005)


@pytest.mark.parametrize("pooled", [False, True], ids=["inline", "pooled"])
def test_slot_is_not_rewritten_before_its_upload_completes(synth, pooled,
                                                           request):
    from cst_captioning_tpu.data.prefetch import StagingRing, prefetch_to_device

    if pooled:      # two rows a batch: a block a resnet row, c3d in one call
        request.getfixturevalue("small_blocks")
    blocks0 = _blocks()
    ds = _open(synth, True)
    batcher = Batcher(ds, batch_size=2, max_len=7, mode="video", seed=1)
    assert batcher.num_batches() == 6
    ring = StagingRing(0)                       # two slots
    host, uploads = [], []

    def transform(b):
        host.append(b.feats["resnet"])
        uploads.append(_SlowUpload(b.feats["resnet"].copy()))
        return uploads[-1]

    it = prefetch_to_device(batcher.epoch(staging=ring), size=1, place=False,
                            transform=transform, staging=ring)
    assert next(it) is uploads[0]
    # batch 1 goes to the other slot; batch 2 wants slot 0 back
    assert next(it) is uploads[1]
    _wait_for(lambda: uploads[0].waited)
    time.sleep(0.1)
    assert len(uploads) == 2, "collated into a slot whose upload is in flight"
    np.testing.assert_array_equal(host[0], uploads[0].snapshot)
    uploads[1].done.set()                       # not the one it waits on
    time.sleep(0.05)
    assert len(uploads) == 2
    uploads[0].done.set()
    assert next(it) is uploads[2]
    assert host[2] is host[0]                   # the slot, reused
    assert not np.array_equal(host[0], uploads[0].snapshot)
    uploads[2].done.set()
    for u in it:                                # and does proceed after
        u.done.set()
    assert len(uploads) == 6
    assert _blocks() - blocks0 == (6 * 2 if pooled else 0)
    ds.close()


def test_ring_gives_up_a_slot_it_cannot_fence(synth):
    """A placement that reads the slot's memory in place (numpy views let
    through; the CPU backend's zero-copy device_put) or whose arrays were
    deleted before the fence: the batch keeps the arrays, the slot starts
    over, nothing raises."""
    import jax

    from cst_captioning_tpu.data.prefetch import StagingRing, prefetch_to_device

    ds = _open(synth, True)
    kw = dict(batch_size=4, max_len=7, mode="video", seed=1)
    refs = _reference_epoch(_open(synth, False), seq_per_vid=1, salt=0,
                            epoch_index=0, host_shard=(0, 1), **kw)

    # (1) host arrays let through un-placed
    ring = StagingRing(0)
    kept = list(prefetch_to_device(Batcher(ds, **kw).epoch(staging=ring),
                                   size=1, place=False, staging=ring,
                                   transform=_flat))
    for got, ref in zip(kept, refs, strict=True):
        _assert_same(got, ref)

    # (2) a 64-byte-aligned slot array: the CPU backend aliases it
    ring = StagingRing(0)
    slot = ring.acquire()
    raw = np.zeros(4096 + 64, np.uint8)
    off = (-raw.ctypes.data) % 64
    slot["a"] = raw[off:off + 4096].view(np.float32)
    placed = jax.device_put(slot["a"])
    aliased = placed.unsafe_buffer_pointer() == slot["a"].ctypes.data
    ring.uploaded(placed)
    ring.acquire()
    assert (ring.acquire() == {}) == aliased    # slot 0 again: given up iff read in place

    # (3) deleted before the fence
    ring = StagingRing(0)
    slot = ring.acquire()
    slot["a"] = np.ones((3,), np.float32)       # 12 bytes: copied, not aliased
    placed = jax.device_put(slot["a"]).copy()
    ring.uploaded(placed)
    placed.delete()
    ring.acquire()
    assert ring.acquire() == {}
    ring.settle()
    ds.close()


def test_plain_iteration_owns_its_arrays(synth):
    ds = _open(synth, True)
    it = iter(Batcher(ds, batch_size=4, max_len=7, mode="caption", seed=1))
    first = next(it)
    snap = {k: np.array(v) for k, v in _flat(first).items() if k != "video_ids"}
    second = next(it)
    for k, want in snap.items():
        have = _flat(first)[k]
        assert have.flags.writeable
        assert not np.shares_memory(have, _flat(second)[k])
        np.testing.assert_array_equal(have, want)
    first.feats["resnet"][:] = -1.0             # the owner may write
    third = next(it)
    assert not (third.feats["resnet"] == -1.0).all()
    ds.close()


def test_prefetch_retires_worker_with_slots_outstanding(synth):
    """Early abandon and an upstream error, with uploads still unfenced."""
    from cst_captioning_tpu.data.prefetch import StagingRing, prefetch_to_device

    ds = _open(synth, True)
    n_before = threading.active_count()
    ring = StagingRing(2)
    batcher = Batcher(ds, batch_size=2, max_len=7, mode="video", seed=1)

    it = prefetch_to_device(batcher.epoch(staging=ring), size=2, staging=ring,
                            transform=lambda b: (b.feats, b.feat_masks))
    next(it)
    it.close()
    _wait_for(lambda: threading.active_count() <= n_before)
    assert ring._pending == [None] * 4          # settled: nothing held on

    def failing():
        for k, b in enumerate(batcher.epoch(staging=ring)):
            if k == 2:
                raise RuntimeError("boom")
            yield b

    with pytest.raises(RuntimeError, match="boom"):
        list(prefetch_to_device(failing(), size=2, staging=ring,
                                transform=lambda b: (b.feats, b.feat_masks)))
    _wait_for(lambda: threading.active_count() <= n_before)
    assert ring._pending == [None] * 4
    # and the ring still serves a whole epoch after both
    got = list(prefetch_to_device(batcher.epoch(staging=ring), size=2,
                                  staging=ring,
                                  transform=lambda b: (b.feats, b.feat_masks)))
    assert len(got) == batcher.num_batches()
    ds.close()


def test_collate_counters_and_take_mode(synth, monkeypatch, request):
    from cst_captioning_tpu import obs
    from cst_captioning_tpu.data import batcher as batcher_module
    from cst_captioning_tpu.data.prefetch import StagingRing

    calls = []
    real_take = np.take

    def take(a, indices, axis=None, out=None, mode="raise"):
        calls.append((out is not None, mode,
                      threading.current_thread().name))
        return real_take(a, indices, axis=axis, out=out, mode=mode)

    monkeypatch.setattr(np, "take", take)

    def counts():
        c = obs.snapshot()["counters"]
        return (c.get("data.collate.staged", 0), c.get("data.collate.fresh", 0))

    ds = _open(synth, True)
    batcher = Batcher(ds, batch_size=4, max_len=7, mode="video", seed=1)
    n = batcher.num_batches()
    s0, f0 = counts()
    blocks0 = _blocks()
    list(batcher.epoch())                       # plain: all fresh
    assert counts() == (s0, f0 + n)
    ring = StagingRing(0)                       # two slots: two first fills
    list(batcher.epoch(staging=ring))
    assert counts() == (s0 + n - 2, f0 + n + 2)
    list(batcher.epoch(staging=ring))           # warm ring: all staged
    assert counts() == (s0 + 2 * n - 2, f0 + n + 2)
    # a gather with out= never runs under mode="raise" (it would buffer
    # the whole batch through a temporary): features and frame mask of each
    # stream, then labels, mask and weights, every one a call on this thread
    assert len(calls) == 3 * n * (2 * len(_BOTH) + 3)
    assert all(has_out and mode != "raise" for has_out, mode, _ in calls)
    me = threading.current_thread().name
    assert {name for _, _, name in calls} == {me}
    assert _blocks() == blocks0                 # no block ran on the pool
    # with the pool engaged: four rows a batch are four blocks of resnet and
    # two of c3d, on the pool's threads and on no other; the rest stays here
    request.getfixturevalue("small_blocks")
    del calls[:]
    list(batcher.epoch(staging=ring))
    assert _blocks() - blocks0 == n * (4 + 2)
    assert len(calls) == n * (4 + 2 + len(_BOTH) + 3)
    assert all(has_out and mode != "raise" for has_out, mode, _ in calls)
    pool_calls = [name for _, _, name in calls if name != me]
    assert len(pool_calls) == n * (4 + 2)
    assert all(name.startswith("collate.gather") for name in pool_calls)
    width = obs.snapshot()["gauges"]["data.collate.pool_width"]
    assert width == batcher_module._gather_pool()[1] >= 2
    assert counts() == (s0 + 3 * n - 2, f0 + n + 2)
    ds.close()


# ---- one prefetch feed for a run of epochs -----------------------------------

_FEED_KW = dict(batch_size=4, max_len=7, mode="caption", seq_per_vid=3, seed=2)
_FEED_COUNTERS = ("prefetch.epoch.cold", "prefetch.epoch.carried",
                  "prefetch.dropped")


def _feed_counts():
    from cst_captioning_tpu import obs

    return np.array([obs.counter(n).snapshot() for n in _FEED_COUNTERS])


def _placed(b):
    """The RL transform's shape: arrays placed, the ids left on the host."""
    import jax

    flat = dict(_flat(b))
    ids = flat.pop("video_ids")
    return jax.device_put(flat), list(ids)


def _cold_epoch(ds, salt, epoch_index, skip=0, **kw):
    """The batches of a cold ``Batcher.epoch()`` drawn the old way: a fresh
    batcher whose own attributes carry the key."""
    b = Batcher(ds, **{**_FEED_KW, **kw})
    b.salt, b.epoch_index = salt, epoch_index
    out = [_flat(x) for x in b.epoch()][skip:]
    assert b.epoch_index == epoch_index + 1
    return out


def _assert_epoch(got, refs):
    import jax

    assert len(got) == len(refs)
    for (arrays, ids), ref in zip(got, refs, strict=True):
        _assert_same({**jax.device_get(arrays), "video_ids": ids}, ref)


def _feed(ring, size, **kw):
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import PrefetchFeed

    return PrefetchFeed(lambda key: key.batches(ring), EpochKey.following,
                        size=size, transform=_placed, place=False,
                        staging=ring, **kw)


def _no_prefetch_thread(n_before):
    _wait_for(lambda: threading.active_count() <= n_before)
    assert not [t for t in threading.enumerate() if t.name == "prefetch"]


@pytest.mark.parametrize("ringed", [True, False], ids=["ring", "fresh"])
@pytest.mark.parametrize("size", [0, 1, 2])
def test_feed_yields_each_cold_epochs_batches(synth, size, ringed):
    """Three epochs through one feed, every item kept to the end: epoch by
    epoch exactly the batches (ids, valid, feature and label bytes) of three
    cold Batcher.epoch() generators with the same (seed, salt, epoch_index);
    the batcher's own index is left alone; one worker served all three."""
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import StagingRing

    ds, plain = _open(synth, True), _open(synth, False)
    n_before = threading.active_count()
    batcher = Batcher(ds, **_FEED_KW)
    batcher.epoch_index = 99                    # the main thread's: not used
    ring = StagingRing(size) if ringed else None
    feed = _feed(ring, size)
    c0 = _feed_counts()
    got = [list(feed.epoch(EpochKey(batcher, 1, e, 0, 8))) for e in (5, 6, 7)]
    assert batcher.epoch_index == 99
    _no_prefetch_thread(n_before)               # index 7 was the run's last
    for e, epoch in zip((5, 6, 7), got):
        _assert_epoch(epoch, _cold_epoch(plain, salt=1, epoch_index=e))
    assert len(got[0]) > size + 2
    # cold once (inline: every epoch), carried ever after, nothing thrown away
    want = [1, 2, 0] if size else [3, 0, 0]
    assert (_feed_counts() - c0).tolist() == want
    if ring is not None:
        assert ring._pending == [None] * (size + 2)
    ds.close()
    plain.close()


@pytest.mark.parametrize("changed", ["salt", "epoch_index", "skip", "batcher"])
def test_feed_drops_what_was_staged_for_another_epoch(synth, changed):
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import StagingRing

    ds, plain = _open(synth, True), _open(synth, False)
    n_before = threading.active_count()
    batcher = Batcher(ds, **_FEED_KW)
    ring = StagingRing(2)
    settles = []
    settle = ring.settle
    ring.settle = lambda: (settles.append(1), settle())[1]
    feed = _feed(ring, 2)
    key = EpochKey(batcher, 0, 0, 0, 10)
    c0 = _feed_counts()
    first = list(feed.epoch(key))
    _assert_epoch(first, _cold_epoch(plain, salt=0, epoch_index=0))
    # the worker is on epoch 1 by now: let it fill the queue
    _wait_for(lambda: feed._worker.q.full())
    assert not settles
    asked = {
        "salt": key._replace(salt=3, index=1),          # a rollback's re-salt
        "epoch_index": key,                             # ... and its rewind
        "skip": key._replace(index=1, skip=2),          # a resume mid-epoch
        "batcher": key._replace(index=1, batcher=Batcher(ds, **_FEED_KW)),
    }[changed]
    got = list(feed.epoch(asked))
    _assert_epoch(got, _cold_epoch(plain, salt=asked.salt,
                                   epoch_index=asked.index, skip=asked.skip))
    cold, carried, dropped = (_feed_counts() - c0).tolist()
    assert (cold, carried) == (2, 0)
    assert 2 <= dropped <= 3                    # the queue's two, one in hand
    assert settles                              # by the worker that was retired
    # and from there the feed carries on as from any first epoch
    nxt = asked.following()
    _assert_epoch(list(feed.epoch(nxt)),
                  _cold_epoch(plain, salt=nxt.salt, epoch_index=nxt.index))
    assert (_feed_counts() - c0).tolist()[:2] == [2, 1]
    feed.close()
    _no_prefetch_thread(n_before)
    assert ring._pending == [None] * 4
    ds.close()
    plain.close()


def test_feed_stops_at_the_last_epoch_and_on_stop_event(synth):
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import PrefetchFeed, StagingRing

    ds = _open(synth, True)
    n_before = threading.active_count()
    batcher = Batcher(ds, **_FEED_KW)
    n = batcher.num_batches()
    ring = StagingRing(1)
    drawn, pulled = [], []

    def draw(key):
        drawn.append(key.index)
        for b in key.batches(ring):
            pulled.append(key.index)
            yield b

    # (a) told that index 1 is the last: nothing of index 2 is drawn
    feed = PrefetchFeed(draw, EpochKey.following, size=1, transform=_placed,
                        place=False, staging=ring)
    for e in (0, 1):
        assert len(list(feed.epoch(EpochKey(batcher, 0, e, 0, 2)))) == n
    _no_prefetch_thread(n_before)
    assert drawn == [0, 1] and feed._worker is None
    assert ring._pending == [None] * 3

    # (b) stop_event while epoch 0 is being consumed: what is staged is
    # still yielded, the epoch then ends, epoch 1 is never begun
    del drawn[:], pulled[:]
    stop = threading.Event()
    feed = PrefetchFeed(draw, EpochKey.following, size=1, transform=_placed,
                        place=False, staging=ring, stop_event=stop)
    c0 = _feed_counts()
    it = feed.epoch(EpochKey(batcher, 0, 0, 0, 5))
    next(it)
    _wait_for(lambda: len(pulled) == 3)         # one queued, one in hand
    stop.set()
    assert len(list(it)) == 2
    _no_prefetch_thread(n_before)
    assert drawn == [0] and pulled == [0] * 3
    assert (_feed_counts() - c0).tolist() == [1, 0, 0]
    assert ring._pending == [None] * 3
    ds.close()


def test_feed_raises_an_error_on_the_batch_it_happened_on(synth):
    """Staging epoch 1's first batch fails while epoch 0 is being consumed:
    epoch 0 ends cleanly, the error comes when epoch 1 asks for that batch,
    and the feed serves the epoch after a repair."""
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import PrefetchFeed, StagingRing

    ds, plain = _open(synth, True), _open(synth, False)
    n_before = threading.active_count()
    batcher = Batcher(ds, **_FEED_KW)
    ring = StagingRing(2)
    broken = {1}
    failed = threading.Event()

    def draw(key):
        for k, b in enumerate(key.batches(ring)):
            if key.index in broken and k == 0:
                failed.set()
                raise RuntimeError("boom")
            yield b

    feed = PrefetchFeed(draw, EpochKey.following, size=2, transform=_placed,
                        place=False, staging=ring)
    key = EpochKey(batcher, 0, 0, 0, 3)
    it = feed.epoch(key)
    head = [next(it) for _ in range(batcher.num_batches() - 1)]
    assert failed.wait(5.0)     # on the worker, epoch 0's last batch not taken
    _assert_epoch(head + list(it), _cold_epoch(plain, salt=0, epoch_index=0))
    c0 = _feed_counts()
    with pytest.raises(RuntimeError, match="boom"):
        next(feed.epoch(key.following()))
    assert (_feed_counts() - c0).tolist() == [0, 1, 0]
    _no_prefetch_thread(n_before)
    assert ring._pending == [None] * 4
    broken.clear()
    _assert_epoch(list(feed.epoch(key.following())),
                  _cold_epoch(plain, salt=0, epoch_index=1))
    feed.close()
    _no_prefetch_thread(n_before)
    ds.close()
    plain.close()


def test_feed_raises_a_gather_blocks_error_on_its_batch(synth, monkeypatch,
                                                        small_blocks):
    """One row block of epoch 1's first batch fails on the pool while epoch
    0 is being consumed: epoch 0 ends cleanly; the error comes when epoch 1
    asks for that batch, and not before every other block of the batch has
    ended; the feed then serves the epoch, its slots rewritten whole."""
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import PrefetchFeed, StagingRing

    ds, plain = _open(synth, True), _open(synth, False)
    batcher = Batcher(ds, **_FEED_KW)
    ring = StagingRing(2)
    broken = {1}
    armed, failed = threading.Event(), threading.Event()
    lock = threading.Lock()
    running = {"begun": 0, "ended": 0}
    real_take = np.take

    def take(a, indices, axis=None, out=None, mode="raise"):
        if not threading.current_thread().name.startswith("collate.gather"):
            return real_take(a, indices, axis=axis, out=out, mode=mode)
        with lock:
            running["begun"] += 1
            boom = armed.is_set()
            armed.clear()
        try:
            if boom:
                failed.set()
                raise RuntimeError("boom")
            if failed.is_set():
                time.sleep(0.02)        # the failed block's slower siblings
            return real_take(a, indices, axis=axis, out=out, mode=mode)
        finally:
            with lock:
                running["ended"] += 1

    monkeypatch.setattr(np, "take", take)

    def draw(key):
        it = key.batches(ring)
        if key.index in broken:
            armed.set()                 # the next pooled block raises
        yield from it

    feed = PrefetchFeed(draw, EpochKey.following, size=2, transform=_placed,
                        place=False, staging=ring)
    key = EpochKey(batcher, 0, 0, 0, 3)
    it = feed.epoch(key)
    head = [next(it) for _ in range(batcher.num_batches() - 1)]
    assert failed.wait(5.0)     # on the pool, epoch 0's last batch not taken
    _assert_epoch(head + list(it), _cold_epoch(plain, salt=0, epoch_index=0))
    c0 = _feed_counts()
    with pytest.raises(RuntimeError, match="boom"):
        next(feed.epoch(key.following()))
    assert running["begun"] == running["ended"] > 0
    assert (_feed_counts() - c0).tolist() == [0, 1, 0]
    _wait_for(lambda: not [t for t in threading.enumerate()
                           if t.name == "prefetch"])
    assert ring._pending == [None] * 4
    broken.clear()
    _assert_epoch(list(feed.epoch(key.following())),
                  _cold_epoch(plain, salt=0, epoch_index=1))
    feed.close()
    assert running["begun"] == running["ended"]
    ds.close()
    plain.close()


def test_one_gather_pool_serves_threads_that_collate_at_once(synth, monkeypatch,
                                                            small_blocks):
    """More collating threads than cores, the interpreter switching every
    few bytecodes: all of them get the one pool, however many ask for it
    first at the same moment, and each one's batches are the reference's."""
    import os
    import sys

    from cst_captioning_tpu.data import batcher as batcher_module

    # a pool not yet made, on a machine taken to have eight cores
    monkeypatch.setattr(batcher_module, "_pool", None)
    monkeypatch.setattr(batcher_module, "_pool_width", 0)
    monkeypatch.setattr(os, "sched_getaffinity", lambda pid: set(range(8)))
    n = 2 * os.cpu_count()
    plain = _open(synth, False)
    kw = dict(batch_size=10, max_len=7, mode="caption", seq_per_vid=2, seed=5)
    refs = _reference_epoch(plain, salt=0, epoch_index=0, host_shard=(0, 1),
                            **kw)
    sets = [_open(synth, True) for _ in range(n)]
    start = threading.Barrier(n)
    pools, errors = [], []

    def work(ds):
        try:
            start.wait(10.0)
            pools.append(batcher_module._gather_pool())
            for _ in range(3):
                got = [_flat(b) for b in Batcher(ds, **kw).epoch(epoch_index=0)]
                for have, ref in zip(got, refs, strict=True):
                    _assert_same(have, ref)
        except BaseException as e:      # surfaced below, on the test's thread
            errors.append(e)

    threads = [threading.Thread(target=work, args=(ds,)) for ds in sets]
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(60.0)
    finally:
        sys.setswitchinterval(interval)
    pool, width = batcher_module._gather_pool()
    pool.shutdown()
    assert not [t for t in threads if t.is_alive()]
    assert not errors, errors
    assert width == 4 and len(pools) == n
    assert all(p is pool and w == 4 for p, w in pools)
    for ds in sets + [plain]:
        ds.close()


@pytest.mark.parametrize("n", [1, 4])
def test_feed_counts_one_cold_epoch_and_the_rest_carried(synth, n):
    from cst_captioning_tpu.data.batcher import EpochKey
    from cst_captioning_tpu.data.prefetch import StagingRing

    ds = _open(synth, True)
    batcher = Batcher(ds, batch_size=4, max_len=7, mode="video", seed=1)
    ring = StagingRing(2)
    feed = _feed(ring, 2)
    c0 = _feed_counts()
    for e in range(n):
        got = list(feed.epoch(EpochKey(batcher, 0, e, 0, n)))
        assert len(got) == batcher.num_batches()
    assert (_feed_counts() - c0).tolist() == [1, n - 1, 0]
    assert feed._worker is None                 # retired with the last epoch
    ds.close()


def test_feed_keeps_every_epochs_items_under_a_short_switch_interval():
    """Many short epochs (some empty) with the interpreter switching threads
    every few bytecodes, the consumer now and then asking for another epoch
    than the one staged ahead or leaving one early: every epoch yields its
    own items in order, and no worker is left behind."""
    import sys

    from cst_captioning_tpu.data.prefetch import PrefetchFeed

    n_before = threading.active_count()
    items = lambda key: [(key, i) for i in range(key % 4)]
    feed = PrefetchFeed(items, lambda key: key + 1 if key % 50 else None,
                        size=2, place=False, stall_warn_s=0)
    rng = np.random.default_rng(0)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        key, deadline = 1, time.monotonic() + 20.0
        for _ in range(400):
            assert time.monotonic() < deadline
            it = feed.epoch(key)
            if rng.random() < 0.1 and key % 4:
                assert next(it) == (key, 0)     # left early: staged, dropped
                it.close()
            else:
                assert list(it) == items(key)
            key = key + 1 if rng.random() < 0.85 else int(rng.integers(1, 500))
        feed.close()
    finally:
        sys.setswitchinterval(interval)
    _no_prefetch_thread(n_before)
