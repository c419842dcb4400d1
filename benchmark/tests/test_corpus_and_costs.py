"""The corpus generator is reproducible from its parameters; the cost model
and the peaks table say what they claim."""

import hashlib
import json
import os

import numpy as np
import pytest

from benchmark import corpus, costs
from benchmark.tests import tiny


def _digest(paths):
    import h5py

    h = hashlib.sha256(open(paths["info_json"], "rb").read())
    for name in sorted(k for k in paths if k != "info_json"):
        with h5py.File(paths[name], "r") as f:
            for vid in sorted(f):
                h.update(np.asarray(f[vid]).tobytes())
    return h.hexdigest()


def test_corpus_is_a_function_of_its_parameters(tmp_path):
    params = tiny.CONFIG["corpus"]
    a = corpus.ensure_corpus(str(tmp_path / "a"), params)
    b = corpus.ensure_corpus(str(tmp_path / "b"), params)
    assert _digest(a) == _digest(b)
    c = corpus.ensure_corpus(str(tmp_path / "c"), dict(params, seed=8))
    assert _digest(c) != _digest(a)
    # reused, not rebuilt, on the second ask
    before = os.path.getmtime(a["info_json"])
    assert corpus.ensure_corpus(str(tmp_path / "a"), params) == a
    assert os.path.getmtime(a["info_json"]) == before


def test_corpus_schema_and_lengths(tmp_path):
    import h5py

    params = tiny.CONFIG["corpus"]
    paths = corpus.ensure_corpus(str(tmp_path), params)
    info = json.load(open(paths["info_json"]))
    assert info["vocab"][:4] == list(corpus.SPECIAL_TOKENS)
    assert len(info["vocab"]) == params["vocab_size"]
    assert len(info["videos"]) == params["videos"]
    lo, hi = params["caption_len"]
    for v in info["videos"]:
        assert v["split"] == "train" and len(v["captions"]) == params["refs_per_video"]
        for ids, raw in zip(v["caption_ids"], v["captions"]):
            assert lo <= len(ids) <= hi and len(raw.split()) == len(ids)
            assert min(ids) >= 4 and max(ids) < params["vocab_size"]
    with h5py.File(paths["resnet"], "r") as f:
        shapes = {f[v["id"]].shape for v in info["videos"]}
    assert {s[1] for s in shapes} == {32}
    assert all(params["min_frames"] <= s[0] <= params["max_frames"] for s in shapes)


MSRVTT = json.load(open(os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "configs", "msrvtt_attention.json")))["model"]


def test_costs_follow_the_stated_conventions():
    enc, tok = costs.enc_and_per_tok_flops(MSRVTT)
    # 2mnk: frame embeddings + memory projection; attention, LSTM, softmax
    assert enc == 2 * 28 * 2548 * 512 + 2 * 56 * 512 * 256
    assert tok == (2 * 512 * 256 + 2 * 56 * 256 + 2 * 56 * 512
                   + 2 * 1024 * 2048 + 2 * 512 * 2048 + 2 * 512 * 9000)
    c = costs.program_cost(MSRVTT, {"kind": "cst", "B": 1792, "K": 5, "chunks": 5})
    assert c["update"]["flops"] == 3 * c["decode"]["flops"]
    x = costs.program_cost(MSRVTT, {"kind": "xe", "B": 64})["xe"]
    assert x["flops"] == 3 * 64 * (enc + 30 * tok)
    assert costs.memory_slots(dict(MSRVTT, encoder="meanpool")) == 2


def test_peaks_raise_for_an_unknown_kind():
    assert costs.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        costs.peaks("cpu")
    least, bound = costs.roofline({"flops": 197e12, "bytes": 1.0}, "TPU v5 lite")
    assert (round(least, 9), bound) == (1.0, "flops")
    least, bound = costs.roofline({"flops": 1.0, "bytes": 819e9}, "TPU v5e")
    assert (round(least, 9), bound) == (1.0, "hbm")
