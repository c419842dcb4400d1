"""The second decoder kind (``ModelConfig.decoder = "latent_moe"``,
models/latent_moe.py) against its plain reference
(benchmark/reference_latent_moe.py), at a small size on seeded random
weights, float32 stated: teacher forcing, prefill then single steps through
the cache, the absorbed decode attention against the expanded form, the
router on a hand-computed case, YaRN's frequencies and softmax scale against
numbers written out here, beam search (both ``beam_impl``s) against the
reference's own, and the shares of an expert layer adding up to the uncut
layer. Then the seams: the ``Evaluator`` places host parameters once and
counts what the decode routed, ``cli/eval.py`` runs the configuration's eval
preset end to end, and ``obs/flops.py`` dispatches on the decoder kind.
"""

import importlib.util
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu import obs
from cst_captioning_tpu.config import get_preset
from cst_captioning_tpu.config.config import ModelConfig
from cst_captioning_tpu.decoding import beam_search
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models import latent_moe
from cst_captioning_tpu.models.captioner import EncoderOutput
from cst_captioning_tpu.obs import flops

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

YARN = (("beta_fast", 1), ("beta_slow", 1), ("factor", 32), ("mscale", 1),
        ("mscale_all_dim", 1), ("original_max_position_embeddings", 4096))
TINY = dict(
    decoder="latent_moe", vocab_size=64, modalities=(("resnet", 32), ("c3d", 16)),
    max_len=12, max_frames=8, dtype="float32", param_dtype="float32",
    hidden_size=32, num_hidden_layers=3, first_k_dense_replace=1,
    intermediate_size=48, moe_intermediate_size=16, n_routed_experts=16,
    n_shared_experts=1, num_experts_per_tok=4, routed_scaling_factor=2.827,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
    qk_rope_head_dim=8, v_head_dim=8, rms_norm_eps=1e-6, rope_theta=50000.0,
    rope_scaling=YARN, experts_held=4, expert_share_index=0,
    initializer_range=0.3)
B, T, F = 6, 12, 8


@pytest.fixture(scope="module")
def ref():
    """The reference, loaded from its file as the harness loads it."""
    spec = importlib.util.spec_from_file_location(
        "reference_latent_moe",
        os.path.join(ROOT, "benchmark", "reference_latent_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _as_file(cfg: ModelConfig) -> dict:
    """The ``model`` dict a configuration file would hold for ``cfg``."""
    return json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))


def _batch(rows=B, seed=1):
    rng = np.random.default_rng(seed)
    n = rng.integers(F // 2, F + 1, size=rows)
    mask = (np.arange(F)[None] < n[:, None]).astype(np.float32)
    feats = {name: (rng.normal(size=(rows, F, dim)) * mask[..., None]
                    ).astype(np.float32) for name, dim in TINY["modalities"]}
    masks = {name: mask.copy() for name, _ in TINY["modalities"]}
    labels = rng.integers(4, TINY["vocab_size"], size=(rows, T)).astype(np.int32)
    return feats, masks, labels


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(**TINY)
    model = CaptionModel(cfg)
    feats, masks, labels = _batch()
    params = model.init(jax.random.key(0), feats, masks, labels)
    return cfg, model, params, feats, masks, labels


def test_init_declares_every_parameter_without_a_forward(setup):
    cfg, _model, params, *_ = setup
    dec = params["params"]["decoder"]
    assert set(dec) == {"embed_resnet", "embed_c3d", "embed_tokens", "norm",
                        "lm_head", "layers_0", "layers_1", "layers_2"}
    assert "gate_proj" in dec["layers_0"] and "gate" not in dec["layers_0"]
    moe = dec["layers_1"]
    assert moe["gate"].shape == (32, 16)            # the router: every expert
    assert moe["experts_gate_proj"].shape == (4, 32, 16)   # the share held
    assert moe["e_score_correction_bias"].dtype == jnp.float32
    assert float(jnp.abs(moe["e_score_correction_bias"]).max()) > 0
    assert float(dec["norm"].min()) == 1.0


def test_teacher_forced_logprobs_match_the_reference(setup, ref):
    cfg, model, params, feats, masks, labels = setup
    logits = jax.jit(model.apply)(params, feats, masks, labels)
    assert logits.shape == (B, T, cfg.vocab_size) and logits.dtype == jnp.float32
    logp = jax.nn.log_softmax(logits, axis=-1)
    got = np.take_along_axis(np.asarray(logp), labels[..., None], -1)[..., 0]
    want = np.asarray(jax.jit(lambda p: ref.token_logprobs(
        p, _as_file(cfg), feats, masks, labels))(params))
    assert np.abs(want).mean() > 1.0        # a peaked distribution, not noise
    np.testing.assert_allclose(got, want, atol=5e-5)


def test_prefill_then_one_token_at_a_time_through_the_cache_match_the_full_forward(
        setup, ref):
    """12 single steps on the carry, on logits, against the reference's full
    forward (which has no cache)."""
    cfg, model, params, feats, masks, labels = setup
    enc = jax.jit(lambda p: model.apply(
        p, feats, masks, method=CaptionModel.encode))(params)
    assert enc.memory.shape == (B, 0) and enc.memory_mask.shape == (B, 2 * F)
    # the cache holds [c_kv | k_r] and nothing else; every leaf is batch-major
    assert all(c.shape == (B, 2 * F + T, 16 + 8) for c in enc.carry.cache)
    assert all(x.shape[0] == B for x in jax.tree.leaves(enc.carry))
    bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask, carry=())
    carry, prev, got = enc.carry, np.full((B,), 1, np.int32), []
    step = jax.jit(lambda p, c, tok: model.apply(
        p, c, tok, bank, method=CaptionModel.decode_step))
    for t in range(T):
        carry, logits = step(params, carry, jnp.asarray(prev))
        got.append(np.asarray(logits))
        prev = labels[:, t]
    assert np.asarray(carry.pos).tolist() == [2 * F + T] * B
    inputs = np.concatenate([np.ones((B, 1), np.int32), labels[:, :-1]], 1)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(jax.jit(lambda p: ref.forward(
            p, _as_file(cfg), feats, masks, jnp.asarray(inputs),
            lambda x: x))(params))
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-4)


def test_absorbed_decode_attention_equals_the_expanded_form(setup):
    """One layer: the last position of the expanded attention over a whole
    sequence, and the absorbed step against a cache of the positions before."""
    cfg, model, params, *_ = setup
    layer = latent_moe.LatentMoELayer(cfg, dense=True)
    p = {"params": params["params"]["decoder"]["layers_0"]}
    rng = np.random.default_rng(3)
    P = 9
    x = jnp.asarray(rng.normal(size=(B, P, cfg.hidden_size)), jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(P), (B, P))
    mask = jnp.broadcast_to(jnp.arange(P)[:, None] >= jnp.arange(P)[None], (B, P, P))
    full, ckv = layer.apply(p, x, pos, mask, method=layer.attend_full)
    cache = jnp.pad(ckv[:, :P - 1], ((0, 0), (0, 4), (0, 0)))
    out, cache = layer.apply(
        p, x[:, -1], cache, jnp.full((B,), P - 1, jnp.int32),
        jnp.ones((B, P + 3), bool), method=layer.attend_step)
    np.testing.assert_allclose(np.asarray(out), np.asarray(full[:, -1]),
                               atol=2e-5)
    np.testing.assert_allclose(np.asarray(cache[:, P - 1]),
                               np.asarray(ckv[:, P - 1]), atol=1e-6)
    assert float(jnp.abs(cache[:, P:]).max()) == 0.0


def test_router_on_a_hand_computed_case():
    """Two tokens, six experts, top 2: a bias that changes the choice and not
    the weights; normalisation over all chosen; the scaling factor."""
    x = jnp.eye(2, dtype=jnp.float32)
    logits = np.log(np.array([[4.0, 1.0, 1 / 3, 1.0, 1.0, 1.0],
                              [1.0, 1.0, 1.0, 1.0, 3.0, 1 / 9]]))
    # sigmoid(log r) = r / (1 + r): scores 0.8 0.5 0.25 ... / 0.5 ... 0.75 0.1
    bias = jnp.asarray([0.0, 0.0, 0.3, 0.0, 0.0, 0.0])
    chosen, w = latent_moe.route(x, jnp.asarray(logits, jnp.float32), bias,
                                 k=2, scale=2.827)
    # token 0: 0.8, then 0.25 + 0.3 = 0.55 > 0.5: expert 2 is chosen by the
    # bias, and weighs 0.25, not 0.55. token 1: 0.5 + 0.3 = 0.8 > 0.75
    assert np.asarray(chosen).tolist() == [[0, 2], [2, 4]]
    np.testing.assert_allclose(
        np.asarray(w), [[0.8 / 1.05 * 2.827, 0.25 / 1.05 * 2.827],
                        [0.5 / 1.25 * 2.827, 0.75 / 1.25 * 2.827]], rtol=1e-6)
    # without the bias the choice differs, the weights' rule does not
    chosen0, w0 = latent_moe.route(x, jnp.asarray(logits, jnp.float32),
                                   jnp.zeros(6), k=2, scale=2.827)
    assert np.asarray(chosen0)[0].tolist() == [0, 1]
    np.testing.assert_allclose(np.asarray(w0)[0].sum(), 2.827, rtol=1e-6)


def test_yarn_frequencies_and_softmax_scale_are_the_published_ones(ref):
    """At the published sizes (64 rope dimensions, theta 50000, factor 32 over
    4096 positions, beta_fast = beta_slow = 1): the dimension that makes one
    turn in 4096 positions is 64 * ln(4096 / 2 pi) / (2 ln 50000) = 19.17, so
    pairs 0-19 keep theta^(-2i/64) and pairs 20-31 are slowed 32 times."""
    cfg = get_preset("kimi_k2_ep32_eval_beam5").model
    got = np.asarray(latent_moe.yarn_inv_freq(cfg))
    plain = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    want = np.where(np.arange(32) <= 19, plain, plain / 32)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    assert got[0] == 1.0
    np.testing.assert_allclose(got[19], 1.6225e-3, rtol=1e-3)
    np.testing.assert_allclose(got[20], 3.6140e-5, rtol=1e-3)
    m = 0.1 * math.log(32) + 1          # 1.34657
    np.testing.assert_allclose(latent_moe.softmax_scale(cfg),
                               192 ** -0.5 * m * m, rtol=1e-12)
    np.testing.assert_allclose(latent_moe.softmax_scale(cfg), 0.130862, rtol=1e-5)
    file = json.loads(json.dumps({k: getattr(cfg, k) for k in TINY}))
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(file)), want,
                               rtol=1e-6)
    np.testing.assert_allclose(ref.softmax_scale(file), 0.130862, rtol=1e-5)


@pytest.mark.parametrize("impl", ["lanes", "reference"])
def test_beam_search_emits_the_references_captions(setup, ref, impl):
    cfg, model, params, feats, masks, _labels = setup
    want_tok, want_score = jax.jit(lambda p, f, m: ref.beam_search(
        p, _as_file(cfg), f, m, 5, T))(params, feats, masks)
    tok, score, tally = jax.jit(lambda p, f, m: beam_search(
        model, p, f, m, beam_size=5, max_len=T, beam_impl=impl,
        return_tally=True))(params, feats, masks)
    assert np.array_equal(np.asarray(tok), np.asarray(want_tok))
    np.testing.assert_allclose(np.asarray(score), np.asarray(want_score),
                               atol=1e-4)
    # what the search routed: two expert layers; the last column counts every
    # assignment (4 a token): the prefix's live slots once a clip (the last
    # layer's FFN over the prefix is not run) and 5 lanes a clip a step
    tally = np.asarray(tally)
    live = int(sum(np.asarray(m).sum() for m in masks.values()))
    steps = tally[1, -1] // (4 * 5 * B)
    assert 1 <= steps <= T and tally[1, -1] == 4 * 5 * B * steps
    assert tally[0, -1] == 4 * live + tally[1, -1]
    assert (tally[:, :-1].sum(1) <= tally[:, -1]).all() and tally[:, :-1].sum() > 0


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer(setup, ref):
    """16 experts in 4 shares of 4: the four partial layer outputs, with the
    shared expert counted once, equal the uncut reference's layer (every
    expert held)."""
    cfg, _model, _params, *_ = setup
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(40, cfg.hidden_size)), jnp.float32)
    whole_cfg = ModelConfig(**{**TINY, "experts_held": 16})
    whole_layer = latent_moe.LatentMoELayer(whole_cfg, dense=False)
    live = jnp.ones((40,), bool)
    whole = whole_layer.init(jax.random.key(7), x, live, False,
                             method=whole_layer.ffn)["params"]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.expert_ffn(whole, _as_file(whole_cfg), x,
                                         lambda v: v))
        shared = np.asarray(ref.gated(x, whole["shared_gate_proj"],
                                      whole["shared_up_proj"],
                                      whole["shared_down_proj"], lambda v: v))
    total, rows = shared.copy(), 0
    for share in range(4):
        part_cfg = ModelConfig(**{**TINY, "expert_share_index": share})
        layer = latent_moe.LatentMoELayer(part_cfg, dense=False)
        held = {k: (v[4 * share:4 * share + 4] if k.startswith("experts_") else v)
                for k, v in whole.items()}
        out, tally = jax.jit(lambda p, layer=layer: layer.apply(
            {"params": p}, x, live, False, method=layer.ffn))(held)
        total += np.asarray(out) - shared
        rows += int(tally[:, :-1].sum())
        # the program's share is the reference's share
        with jax.default_matmul_precision("highest"):
            np.testing.assert_allclose(
                np.asarray(out), np.asarray(jax.jit(
                    lambda p, c=part_cfg: ref.expert_ffn(
                        p, _as_file(c), x, lambda v: v))(held)), atol=2e-5)
    np.testing.assert_allclose(total, want, atol=5e-5)
    assert rows == 40 * 4           # every assignment fell on one share


def test_every_token_on_one_expert_drops_none(setup):
    """No capacity: a router that sends every token to the same held expert
    still computes every row (more rows than one tile holds)."""
    cfg, *_ = setup
    layer = latent_moe.LatentMoELayer(cfg, dense=False)
    rng = np.random.default_rng(11)
    n = 600
    x = jnp.asarray(np.abs(rng.normal(size=(n, cfg.hidden_size))), jnp.float32)
    live = jnp.ones((n,), bool)
    p = layer.init(jax.random.key(2), x, live, False, method=layer.ffn)["params"]
    gate = np.zeros((cfg.hidden_size, 16), np.float32)
    gate[:, 2] = 1.0                     # positive inputs: expert 2 always first
    p = dict(p, gate=jnp.asarray(gate),
             e_score_correction_bias=jnp.zeros((16,), jnp.float32))
    assert latent_moe.expert_tile_rows(n, 4, 16) < n
    for differentiable in (False, True):
        _out, tally = jax.jit(lambda q, d=differentiable: layer.apply(
            {"params": q}, x, live, d, method=layer.ffn))(p)
        assert int(tally[:, 2].sum()) == n


def test_teacher_forcing_is_differentiable(setup):
    cfg, model, params, feats, masks, labels = setup

    def loss(p):
        logp = jax.nn.log_softmax(model.apply(p, feats, masks, labels), -1)
        return -jnp.take_along_axis(logp, labels[..., None], -1).mean()

    grads = jax.jit(jax.grad(loss))(params)
    norms = jax.tree.map(lambda g: float(jnp.abs(g).max()), grads)
    dec = norms["params"]["decoder"]
    assert dec["layers_1"]["experts_down_proj"] > 0 and dec["embed_resnet"] > 0


def test_the_lstm_only_entry_points_say_so(setup):
    _cfg, model, params, feats, masks, labels = setup
    enc = model.apply(params, feats, masks, method=CaptionModel.encode)
    with pytest.raises(NotImplementedError, match="latent_moe"):
        model.apply(params, enc, labels, method=CaptionModel.teacher_force_logps)
    with pytest.raises(ValueError, match="rl.enabled"):
        get_preset("kimi_k2_ep32_xe").override(rl__enabled=True)


def test_flops_dispatch_on_the_decoder_kind():
    lstm = get_preset("msrvtt_xe_attention").model
    assert flops.model_xe_flops_per_row(lstm) == flops.xe_flops_per_row(
        T=30, F=28, d_embed=512, d_hidden=512, d_att=256, V=9000,
        feat_dims=(2048, 500), num_layers=1)
    kimi = get_preset("kimi_k2_ep32_xe").model
    per_tok = flops.latent_moe_per_tok_flops(kimi, context=70)
    # 7 layers of attention projections (2 x 101.1 M) + dense FFN (2 x 396 M)
    # + 6 x (router + shared + 0.25 held experts) + the absorbed attention
    assert 2.9e9 < per_tok < 3.1e9
    assert flops.model_xe_flops_per_row(kimi) > 3 * 86 * per_tok * 0.9


# ---- the seams: Evaluator, checkpoints, cli/eval.py ---------------------------


def _tiny_overrides():
    return {"model__" + k: v for k, v in TINY.items()
            if k not in ("decoder",)}


def _dataset(tmp_path, videos: int, splits):
    """A synthetic split ``train`` with the tiny model's modalities (its 44
    words fit the model's 64)."""
    from cst_captioning_tpu.data.dataset import CaptionDataset
    from cst_captioning_tpu.data.synthetic import make_synthetic_dataset

    paths = make_synthetic_dataset(
        str(tmp_path / "data"), num_videos=videos, vocab_words=40,
        modalities=dict(TINY["modalities"]), max_frames=F, splits=splits,
        seed=3)
    names = [n for n, _ in TINY["modalities"]]
    return CaptionDataset(paths["info_json"], {n: paths[n] for n in names},
                          "train", F), paths


def test_evaluator_places_host_parameters_once_and_counts_the_routing(
        tmp_path, setup):
    from cst_captioning_tpu.eval.evaluator import Evaluator

    cfg, model, params, *_ = setup
    ds, _paths = _dataset(tmp_path, 20, (1.0, 0.0))
    ecfg = get_preset("kimi_k2_ep32_eval_beam5").eval
    import dataclasses
    ecfg = dataclasses.replace(ecfg, max_len=T, metrics=("CIDEr-D",),
                               split="train")
    obs.configure(str(tmp_path / "obs"), run="t")
    try:
        ev = Evaluator(model, ds, ecfg, batch_size=8)
        host = jax.device_get(params)
        first = ev.evaluate(host)
        again = ev.evaluate(host)
        placed = ev._placed[1]
        assert all(isinstance(x, jax.Array) for x in jax.tree.leaves(placed))
        # device arrays pass through and place nothing anew
        third = ev.evaluate(params)
        snap = obs.snapshot()
    finally:
        obs.shutdown()
        ds.close()
    assert first["captions"] == again["captions"] == third["captions"]
    assert len(first["captions"]) == 20
    spans = [json.loads(line) for line in open(tmp_path / "obs" / "events.jsonl")]
    places = [e for e in spans if e.get("event") == "span"
              and e["name"] == "eval.params.place"]
    assert len(places) == 1                     # three passes, one upload
    c, g = snap["counters"], snap["gauges"]
    assert g["moe.experts_held"] == 4
    # 8 clips x 5 beams x 3 layers x (16 + 12) positions x 24 numbers x 4 B
    assert g["decode.cache_bytes"] >= 8 * 5 * 3 * 28 * 24 * 4
    assert 0 < c["moe.assignments.local"] < c["moe.assignments"]
    rows = snap["histograms"]["moe.expert_rows"]
    # one observation a batch for each held expert of each expert layer
    assert rows["count"] == 3 * 3 * 2 * 4 and rows["sum"] == c["moe.assignments.local"]


def test_cli_eval_runs_the_eval_preset_end_to_end(tmp_path, capsys):
    """``cli/eval.py`` on the configuration's eval preset (tiny overrides):
    a checkpoint of seeded weights saved by the ``Trainer`` of its XE preset
    (SGD: no moments; ``train_xe(epochs=0)`` is a no-op), loaded and decoded
    at beam 5. No entry point of its own."""
    from cst_captioning_tpu.cli import eval as cli_eval
    from cst_captioning_tpu.train.trainer import Trainer

    over = _tiny_overrides()
    ds, paths = _dataset(tmp_path, 12, (1.0, 0.0))
    cfg = get_preset("kimi_k2_ep32_xe").override(
        **over, data__batch_size=4, train__ckpt_dir=str(tmp_path / "ckpt"))
    trainer = Trainer(cfg, ds, None, use_mesh=False)
    assert jax.tree.leaves(trainer.state.opt_state) == [] or all(
        x.ndim == 0 for x in jax.tree.leaves(trainer.state.opt_state))
    assert trainer.train_xe(epochs=0) is None
    trainer.ckpt.save(jax.device_get(trainer.state), None)
    trainer.close()
    ds.close()
    args = ["--preset", "kimi_k2_ep32_eval_beam5",
            "--info-json", paths["info_json"],
            "--ckpt-dir", str(tmp_path / "ckpt"), "--ckpt-name", "latest",
            "--split", "train", "--results-json", str(tmp_path / "r.json"),
            "--set", "data__batch_size=4", "--set", "eval__max_len=12",
            "--set", "mesh__num_devices=1"]
    for name, _dim in TINY["modalities"]:
        args += ["--feature", f"{name}={paths[name]}"]
    for key, value in over.items():
        args += ["--set", f"{key}={value!r}"]
    cli_eval.main(args)
    table = json.loads(capsys.readouterr().out)
    assert "CIDEr-D" in table and np.isfinite(table["CIDEr-D"])
    with open(tmp_path / "r.json") as f:
        assert len(json.load(f)["captions"]) == 12
