"""Fused decode-step kernel parity (ops/decode_pallas.py).

Off-TPU these run the kernel in Pallas interpret mode — the same kernel
code path Mosaic compiles on TPU (mirrors tests/test_ops_pallas.py's
contract for the attention kernel). The sweep covers {f32, bf16} x
{small odd dims, flagship-ish aligned dims} so both the block-padding
paths (odd B/M/V spanning block boundaries) and the multi-vocab-block grid
(V > block_v) are exercised.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.config.config import ModelConfig
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import CaptionModel as CM
from cst_captioning_tpu.ops.decode_pallas import _reference, fused_decode_step

# (name, B, V, d_embed/hidden, d_att, frames, layers, block_b, block_v)
# small: odd everything, one vocab block; flagship-ish: MXU-aligned dims,
# B spanning two batch blocks, V spanning multiple vocab blocks
DIMS = {
    "small": dict(B=5, V=23, d=12, d_att=6, F=7, L=1, block_b=32,
                  block_v=1024),
    "small-2layer": dict(B=4, V=19, d=10, d_att=6, F=5, L=2, block_b=32,
                         block_v=1024),
    "flagship-ish": dict(B=40, V=1200, d=128, d_att=64, F=10, L=1,
                         block_b=32, block_v=512),
}


def _setup(dims, dtype, K=2, seed=0):
    cfg = ModelConfig(
        vocab_size=dims["V"], modalities=(("resnet", 16),),
        d_embed=dims["d"], d_hidden=dims["d"], d_att=dims["d_att"],
        encoder="temporal_attention", dropout=0.0, max_len=8,
        max_frames=dims["F"], dtype=dtype, num_layers=dims["L"],
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(seed)
    B, F = dims["B"], dims["F"]
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 16)), jnp.float32)}
    masks = {
        "resnet": jnp.asarray(
            np.arange(F)[None, :] < rng.integers(2, F + 1, size=(B, 1)),
            jnp.float32,
        )
    }
    labels = jnp.asarray(rng.integers(4, dims["V"], size=(B, 8)), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    enc = model.apply(params, feats, masks, method=CM.encode)
    G = 1 + K
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (G,) + x.shape), enc.carry
    )
    token = jnp.asarray(rng.integers(1, dims["V"], size=(G, B)), jnp.int32)
    return model, params, enc, carry, token


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 4e-2)])
@pytest.mark.parametrize("name", sorted(DIMS))
def test_fused_step_matches_xla_step(name, dtype, tol):
    """Kernel logits + new carry vs the lane-vmapped XLA decode_step, over
    the {f32, bf16} x {small, flagship-ish} sweep. bf16 tolerance is loose
    by design: the kernel computes in f32 while the XLA path's matmuls run
    in the model dtype."""
    dims = DIMS[name]
    model, params, enc, carry, token = _setup(dims, dtype)

    def one(c, t):
        return model.apply(params, c, t, enc, method=CM.decode_step)

    carry_x, logits_x = jax.vmap(one)(carry, token)
    carry_p, logits_p = fused_decode_step(
        params["params"]["cell"], carry, token,
        enc.memory, enc.memory_proj, enc.memory_mask,
        block_b=dims["block_b"], block_v=dims["block_v"],
    )
    assert logits_p.shape == logits_x.shape and logits_p.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_x), rtol=tol, atol=tol
    )
    for a, b in zip(jax.tree.leaves(carry_p), jax.tree.leaves(carry_x)):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=tol, atol=tol,
        )


def test_kernel_matches_jnp_composite_oracle():
    """The kernel and its plain-jnp composite (_reference — also the
    interpret-mode shard_map fallback) agree tightly: same math, one
    blocked, one not."""
    dims = DIMS["flagship-ish"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    carry_p, logits_p = fused_decode_step(
        cell, carry, token, enc.memory, enc.memory_proj, enc.memory_mask,
        block_b=dims["block_b"], block_v=dims["block_v"],
    )
    carry_r, logits_r = _reference(
        cell, carry, token, enc.memory, enc.memory_proj, enc.memory_mask
    )
    np.testing.assert_allclose(
        np.asarray(logits_p), np.asarray(logits_r), rtol=2e-6, atol=2e-6
    )
    for a, b in zip(jax.tree.leaves(carry_p), jax.tree.leaves(carry_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )


def test_decode_impl_pallas_decodes_identically_f32():
    """End to end: greedy / K-rollout sampling / fused RL decode with
    ``decode_impl="pallas"`` produce the XLA path's exact tokens at f32
    (same params — the kernel reads the cell's own tree, so the parameter
    layout is identical by construction)."""
    from cst_captioning_tpu.decoding import (
        fused_decode, greedy_decode, sample_decode,
    )

    dims = DIMS["small"]
    model, params, *_ = _setup(dims, "float32")
    m_pal = CaptionModel(dataclasses.replace(model.cfg, decode_impl="pallas"))
    feats = {"resnet": jnp.asarray(
        np.random.default_rng(0).normal(size=(dims["B"], dims["F"], 16)),
        jnp.float32,
    )}
    masks = {"resnet": jnp.ones((dims["B"], dims["F"]), jnp.float32)}
    key = jax.random.key(11)

    tg, _ = greedy_decode(model, params, feats, masks)
    tgp, _ = greedy_decode(m_pal, params, feats, masks)
    np.testing.assert_array_equal(np.asarray(tgp), np.asarray(tg))

    ts, _ = sample_decode(model, params, feats, masks, key, num_rollouts=3)
    tsp, _ = sample_decode(m_pal, params, feats, masks, key, num_rollouts=3)
    np.testing.assert_array_equal(np.asarray(tsp), np.asarray(ts))

    fg, _, fs, _ = jax.jit(
        lambda p, f, m, r: fused_decode(m_pal, p, f, m, r, num_rollouts=3)
    )(params, feats, masks, key)
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(tg))
    # the fused family keeps the Gumbel-max stream sample_decode left in PR 39
    _, _, fs_xla, _ = fused_decode(model, params, feats, masks, key,
                                   num_rollouts=3)
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(fs_xla))


def test_decode_impl_pallas_under_sharded_decode():
    """decode_impl='pallas' inside the shard_map RL decode (8-device CPU
    mesh): off-TPU the kernel's interpret mode cannot run under the
    varying-axis check, so the documented composite fallback carries it —
    tokens must still match the single-device pallas decode exactly."""
    from cst_captioning_tpu.rl import make_parallel_rl_decode, make_rl_decode
    from cst_captioning_tpu.train import make_mesh, shard_batch

    dims = DIMS["small"]
    model, params, *_ = _setup(dims, "float32")
    m_pal = CaptionModel(dataclasses.replace(model.cfg, decode_impl="pallas"))
    rng = np.random.default_rng(2)
    B = 8  # divisible by the test mesh
    feats = {"resnet": jnp.asarray(
        rng.normal(size=(B, dims["F"], 16)), jnp.float32
    )}
    masks = {"resnet": jnp.ones((B, dims["F"]), jnp.float32)}
    key = jax.random.key(13)
    g1, s1 = make_rl_decode(m_pal, 2, max_len=6)(params, feats, masks, key)
    mesh = make_mesh()
    g2, s2 = make_parallel_rl_decode(m_pal, mesh, 2, max_len=6)(
        params, *shard_batch(mesh, (feats, masks)), key
    )
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g1))
    assert s2.shape == s1.shape


def test_decode_impl_config_validation():
    import pytest as _pytest

    from cst_captioning_tpu.config.config import ExperimentConfig, MeshConfig

    with _pytest.raises(ValueError, match="decode_impl"):
        ModelConfig(decode_impl="mosaic")
    with _pytest.raises(ValueError, match="frame-sharded"):
        ModelConfig(decode_impl="pallas", seq_axis="seq")
    with _pytest.raises(ValueError, match="sequence-parallel"):
        ExperimentConfig(
            model=ModelConfig(decode_impl="pallas"),
            mesh=MeshConfig(seq_devices=2),
        )


def test_kernel_is_inference_only():
    """No VJP: decode never takes gradients; differentiating raises instead
    of silently recomputing."""
    dims = DIMS["small"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]

    def loss(mem):
        _, logits = fused_decode_step(
            cell, carry, token, mem, enc.memory_proj, enc.memory_mask
        )
        return jnp.sum(logits)

    with pytest.raises(Exception):
        jax.grad(loss)(enc.memory)


# ---- multi-step stride kernel (in-kernel token selection) -------------------

def _eos_biased(dims, dtype, seed=0):
    """_setup plus an EOS logit nudge so lanes finish raggedly (compaction
    and the kernel's per-step lane skip get exercised), returning the
    decode-level inputs too."""
    cfg = ModelConfig(
        vocab_size=dims["V"], modalities=(("resnet", 16),),
        d_embed=dims["d"], d_hidden=dims["d"], d_att=dims["d_att"],
        encoder="temporal_attention", dropout=0.0, max_len=8,
        max_frames=dims["F"], dtype=dtype, num_layers=dims["L"],
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(seed)
    B, F = dims["B"], dims["F"]
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 16)), jnp.float32)}
    masks = {
        "resnet": jnp.asarray(
            np.arange(F)[None, :] < rng.integers(2, F + 1, size=(B, 1)),
            jnp.float32,
        )
    }
    labels = jnp.asarray(rng.integers(4, dims["V"], size=(B, 8)), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    from cst_captioning_tpu.config.config import EOS_ID

    bias = params["params"]["cell"]["out_proj"]["bias"]
    params["params"]["cell"]["out_proj"]["bias"] = bias.at[EOS_ID].add(1.0)
    return model, params, feats, masks


def _near_tie_check(model, params, feats, masks, key, ref, got,
                    sel_tol, lp_tol, temperature=1.0):
    """Verify the in-kernel selection's parity contract: wherever the
    Pallas decode's tokens differ from the XLA path's, the FIRST divergence
    on that row must be an argmax near-tie — the kernel's token scores
    within ``sel_tol`` of the XLA-best token's score on the same decoded
    prefix. (Kernel and XLA logits differ by accumulation order; a flipped
    near-tie then conditions every later token, which is the entire
    ``fused_pallas_token_match_frac < 1`` story.) Lanes with identical
    tokens must also match logprobs within ``lp_tol``. Returns the number
    of divergent rows so callers can bound the flip rate."""
    from cst_captioning_tpu.decoding.common import (
        forbid_special, gumbel_step_noise, rollout_step_keys,
    )

    g_ref, glp_ref, s_ref, slp_ref = [np.asarray(x) for x in ref]
    g_got, glp_got, s_got, slp_got = [np.asarray(x) for x in got]
    K, B, T = s_ref.shape
    enc = model.apply(params, feats, masks, method=CM.encode)
    step_keys = rollout_step_keys(key, K, T)
    lanes = [(None, g_ref, g_got, glp_ref, glp_got)] + [
        (k, s_ref[k], s_got[k], slp_ref[k], slp_got[k]) for k in range(K)
    ]
    divergent = 0
    for k, tr, tg, lr, lg in lanes:
        if np.array_equal(tr, tg):
            np.testing.assert_allclose(lr, lg, atol=lp_tol, rtol=lp_tol)
            continue
        # teacher-force the KERNEL's tokens through the XLA model: at the
        # first divergence the prefixes agree, so these are the logits the
        # XLA path would have selected from
        logits = np.asarray(forbid_special(model.apply(
            params, enc, jnp.asarray(tg), method=CM.decode_logits
        ).astype(jnp.float32)))
        V = logits.shape[-1]
        for b in range(B):
            if np.array_equal(tr[b], tg[b]):
                continue
            divergent += 1
            t = int(np.argmax(tr[b] != tg[b]))
            sel = logits[b, t].astype(np.float64)
            if k is not None:
                noise = np.asarray(gumbel_step_noise(
                    step_keys[t], (B, V), jnp.float32
                ))[k, b].astype(np.float64)
                sel = sel / temperature + noise
            gap = float(sel.max() - sel[tg[b, t]])
            assert gap <= sel_tol, (
                f"lane={k} row={b} step={t}: kernel picked {tg[b, t]} "
                f"(score gap {gap:.3e} > {sel_tol}) — not a near-tie; "
                "in-kernel selection semantics diverged"
            )
    return divergent


@pytest.mark.parametrize("dtype,sel_tol,lp_tol", [
    ("float32", 1e-3, 1e-4),
    ("bfloat16", 0.3, 0.1),
])
@pytest.mark.parametrize("name", sorted(DIMS))
def test_stride_kernel_parity_sweep(name, dtype, sel_tol, lp_tol):
    """{f32, bf16} x {small, small-2layer, flagship-ish}: the stride kernel
    (in-kernel selection + compaction prefix) against the stride-1
    uncompacted XLA loop. Tokens must match except at pinned argmax
    near-ties (the documented 0.9998-match-frac cause — see README); the
    bf16 rows run the kernel's f32 compute against bf16 XLA matmuls, the
    loosest corner of the contract."""
    from cst_captioning_tpu.decoding import fused_decode

    dims = DIMS[name]
    model, params, feats, masks = _eos_biased(dims, dtype)
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=3, decode_compact=True,
    ))
    key = jax.random.key(17)
    ref = fused_decode(
        model, params, feats, masks, key, num_rollouts=2,
        decode_stride=1, compact=False,
    )
    got = fused_decode(m_pal, params, feats, masks, key, num_rollouts=2)
    divergent = _near_tie_check(
        model, params, feats, masks, key, ref, got, sel_tol, lp_tol
    )
    # near-ties are rare: most rows must decode identically
    assert divergent <= max(1, dims["B"] // 4), divergent


def test_stride_kernel_matches_composite_oracle():
    """fused_decode_stride (blocked, online-lse, one-hot embed select) vs
    _reference_stride (plain jnp, full logsumexp): same selection semantics,
    one blocked, one not — tokens equal, logprobs/carry tight."""
    from cst_captioning_tpu.decoding.common import (
        gumbel_step_noise, rollout_step_keys,
    )
    from cst_captioning_tpu.ops.decode_pallas import (
        _reference_stride, fused_decode_stride,
    )

    dims = DIMS["flagship-ish"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    G, B = token.shape
    S, V = 3, dims["V"]
    key = jax.random.key(3)
    step_keys = rollout_step_keys(key, G - 1, S)
    noise = jax.vmap(
        lambda ks: gumbel_step_noise(ks, (B, V), jnp.float32)
    )(step_keys)
    finished = jnp.zeros((G, B), bool)
    c_k, tok_k, lp_k = fused_decode_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, jnp.int32(0), steps=S,
        block_b=dims["block_b"], block_v=dims["block_v"],
    )
    c_r, tok_r, lp_r = _reference_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, jnp.int32(0), steps=S, temperature=1.0,
        min_len=0,
    )
    np.testing.assert_array_equal(np.asarray(tok_k), np.asarray(tok_r))
    np.testing.assert_allclose(
        np.asarray(lp_k), np.asarray(lp_r), rtol=2e-5, atol=2e-5
    )
    for a, b in zip(jax.tree.leaves(c_k), jax.tree.leaves(c_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-5, atol=2e-5
        )


def test_stride_kernel_respects_finished_and_n_active():
    """Rows born finished emit PAD/0 from step one; batch blocks past the
    compaction prefix pass their carry through untouched."""
    from cst_captioning_tpu.decoding.common import (
        gumbel_step_noise, rollout_step_keys,
    )
    from cst_captioning_tpu.ops.decode_pallas import fused_decode_stride

    dims = DIMS["flagship-ish"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    G, B = token.shape
    S, V = 2, dims["V"]
    key = jax.random.key(4)
    noise = jax.vmap(
        lambda ks: gumbel_step_noise(ks, (B, V), jnp.float32)
    )(rollout_step_keys(key, G - 1, S))
    # columns past n_active are fully finished; block_b=32 splits B=40 into
    # an active block and a (fully finished) skipped block
    n_active = 32
    finished = jnp.broadcast_to(jnp.arange(B) >= n_active, (G, B))
    c_k, tok_k, lp_k = fused_decode_stride(
        cell, carry, token, finished, enc.memory, enc.memory_proj,
        enc.memory_mask, noise, jnp.int32(0), jnp.int32(n_active), steps=S,
        block_b=dims["block_b"], block_v=dims["block_v"],
    )
    tok_k, lp_k = np.asarray(tok_k), np.asarray(lp_k)
    from cst_captioning_tpu.config.config import PAD_ID

    assert (tok_k[:, :, n_active:] == PAD_ID).all()
    assert (lp_k[:, :, n_active:] == 0.0).all()
    for (c_new, h_new), (c_old, h_old) in zip(c_k, carry):
        np.testing.assert_array_equal(
            np.asarray(c_new[:, n_active:]), np.asarray(c_old[:, n_active:])
        )
        np.testing.assert_array_equal(
            np.asarray(h_new[:, n_active:]), np.asarray(h_old[:, n_active:])
        )
    # active rows decoded something real
    assert (tok_k[0, :, :n_active] != PAD_ID).any()


def test_stride_kernel_under_sharded_decode():
    """The stride path inside the shard_map RL decode (8-device CPU mesh):
    off-TPU the kernel's interpret mode cannot run under the varying-axis
    check, so the documented composite fallback (_reference_stride) carries
    it — greedy tokens must still match the single-device stride decode."""
    from cst_captioning_tpu.rl import make_parallel_rl_decode, make_rl_decode
    from cst_captioning_tpu.train import make_mesh, shard_batch

    dims = DIMS["small"]
    model, params, *_ = _setup(dims, "float32")
    m_pal = CaptionModel(dataclasses.replace(
        model.cfg, decode_impl="pallas", decode_stride=3, decode_compact=True,
    ))
    rng = np.random.default_rng(2)
    B = 8  # divisible by the test mesh
    feats = {"resnet": jnp.asarray(
        rng.normal(size=(B, dims["F"], 16)), jnp.float32
    )}
    masks = {"resnet": jnp.ones((B, dims["F"]), jnp.float32)}
    key = jax.random.key(13)
    g1, s1 = make_rl_decode(m_pal, 2, max_len=6)(params, feats, masks, key)
    mesh = make_mesh()
    g2, s2 = make_parallel_rl_decode(m_pal, mesh, 2, max_len=6)(
        params, *shard_batch(mesh, (feats, masks)), key
    )
    np.testing.assert_array_equal(np.asarray(g2), np.asarray(g1))
    assert s2.shape == s1.shape


def test_stride_kernel_per_row_mem_lens():
    """Per-row raggedness (the serving paged-bank contract): passing
    ``mem_lens`` must equal decoding against a bank whose mask is zeroed
    past each row's length — a row's excluded tail leaves the softmax with
    an exact-zero weight either way, so tokens AND logprobs are
    bit-identical, not merely close. Also pins the composite oracle."""
    from cst_captioning_tpu.decoding.common import (
        gumbel_step_noise, rollout_step_keys,
    )
    from cst_captioning_tpu.ops.decode_pallas import (
        _reference_stride, fused_decode_stride,
    )

    dims = DIMS["small"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    G, B = token.shape
    M = enc.memory.shape[1]
    S, V = 3, dims["V"]
    rng = np.random.default_rng(5)
    # adversarial raggedness: 1-slot and full-length rows interleaved
    lens = np.asarray([1, M, 2, M, 1][:B], np.int32)
    noise = jax.vmap(
        lambda ks: gumbel_step_noise(ks, (B, V), jnp.float32)
    )(rollout_step_keys(jax.random.key(6), G - 1, S))
    finished = jnp.zeros((G, B), bool)
    # the bank every offline caller would build: mask 0 past each length
    # (values scrambled past the length to prove they are unobservable)
    col = np.arange(M)[None, :]
    mask_cut = jnp.asarray(
        np.asarray(enc.memory_mask) * (col < lens[:, None])
    )
    scramble = jnp.asarray(
        np.where((col < lens[:, None])[..., None], np.asarray(enc.memory),
                 rng.normal(size=enc.memory.shape)), enc.memory.dtype
    )
    args = (cell, carry, token, finished)
    kw = dict(noise=noise, t0=jnp.int32(0), steps=S,
              block_b=dims["block_b"], block_v=dims["block_v"])
    c_l, tok_l, lp_l = fused_decode_stride(
        *args, scramble, enc.memory_proj, mask_cut, mem_lens=jnp.asarray(lens),
        **kw,
    )
    c_m, tok_m, lp_m = fused_decode_stride(
        *args, enc.memory * mask_cut[..., None], enc.memory_proj, mask_cut,
        **kw,
    )
    np.testing.assert_array_equal(np.asarray(tok_l), np.asarray(tok_m))
    np.testing.assert_array_equal(np.asarray(lp_l), np.asarray(lp_m))
    for a, b in zip(jax.tree.leaves(c_l), jax.tree.leaves(c_m)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # composite oracle honors mem_lens identically (the interpret-mode
    # shard_map fallback serving relies on)
    c_r, tok_r, lp_r = _reference_stride(
        cell, carry, token, finished, scramble, enc.memory_proj, mask_cut,
        noise, jnp.int32(0), steps=S, temperature=1.0, min_len=0,
        mem_lens=jnp.asarray(lens),
    )
    np.testing.assert_array_equal(np.asarray(tok_r), np.asarray(tok_l))
    np.testing.assert_allclose(
        np.asarray(lp_r), np.asarray(lp_l), rtol=2e-5, atol=2e-5
    )


def _page_scatter(enc, lens, page_size, width, num_pages, seed=0):
    """Chop each row's encoder bank into ``page_size``-slot pages scattered
    over an ``[N+1, P, *]`` pool (row 0 = shared zero page) in a random pool
    order, returning (mem_pool, proj_pool, mask_pool, table)."""
    rng = np.random.default_rng(seed)
    B, M, E = enc.memory.shape
    A = enc.memory_proj.shape[2]
    P, W = page_size, width * page_size
    mem_pool = np.zeros((num_pages + 1, P, E), np.float32)
    proj_pool = np.zeros((num_pages + 1, P, A), np.float32)
    mask_pool = np.zeros((num_pages + 1, P), np.float32)
    table = np.zeros((B, width), np.int32)
    free = list(rng.permutation(np.arange(1, num_pages + 1)))
    mem = np.asarray(enc.memory)
    proj = np.asarray(enc.memory_proj)
    mask = np.asarray(enc.memory_mask)
    for b in range(B):
        L_b = int(lens[b])
        npg = -(-L_b // P)
        memb = np.zeros((npg * P, E), np.float32)
        projb = np.zeros((npg * P, A), np.float32)
        maskb = np.zeros((npg * P,), np.float32)
        memb[:L_b] = mem[b, :L_b]
        projb[:L_b] = proj[b, :L_b]
        maskb[:L_b] = mask[b, :L_b]
        for p in range(npg):
            pg = free.pop()
            table[b, p] = pg
            mem_pool[pg] = memb[p * P:(p + 1) * P]
            proj_pool[pg] = projb[p * P:(p + 1) * P]
            mask_pool[pg] = maskb[p * P:(p + 1) * P]
    return (
        jnp.asarray(mem_pool), jnp.asarray(proj_pool),
        jnp.asarray(mask_pool), jnp.asarray(table),
    )


@pytest.mark.parametrize("name,n_active", [
    ("small-2layer", None), ("small-2layer", 3), ("flagship-ish", None),
    ("flagship-ish", 33),
])
def test_paged_stride_bit_exact_vs_dense_gather(name, n_active):
    """THE paged-attention acceptance pin: fused_decode_stride_paged
    (in-kernel page-table DMA, no dense bank) vs fused_decode_stride on the
    _gather_pages dense reference — identical math on identical bytes, so
    tokens, logprobs AND carry are bit-identical, not merely close. Ragged
    per-row lens, randomly scattered pool pages, zero-page-padded tails,
    and a compaction prefix (n_active < B) are all in the sweep."""
    from cst_captioning_tpu.ops.decode_pallas import (
        _gather_pages, fused_decode_stride, fused_decode_stride_paged,
    )

    dims = DIMS[name]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    G, B = token.shape
    M = enc.memory.shape[1]
    S, V = 3, dims["V"]
    rng = np.random.default_rng(7)
    lens = np.asarray(
        [1, M] + list(rng.integers(1, M + 1, size=B - 2)), np.int32
    )
    P = 3
    width = -(-M // P)
    pool_pages = int(sum(-(-int(l) // P) for l in lens)) + 5
    mem_pool, proj_pool, mask_pool, table = _page_scatter(
        enc, lens, P, width, pool_pages, seed=11
    )
    from cst_captioning_tpu.decoding.common import (
        gumbel_step_noise, rollout_step_keys,
    )
    noise = jax.vmap(
        lambda ks: gumbel_step_noise(ks, (B, V), jnp.float32)
    )(rollout_step_keys(jax.random.key(8), G - 1, S))
    n = B if n_active is None else n_active
    finished = jnp.broadcast_to(jnp.arange(B) >= n, (G, B))
    lens_d = jnp.asarray(lens)
    kw = dict(steps=S, temperature=0.8, min_len=1,
              num_layers=dims["L"], block_b=dims["block_b"],
              block_v=dims["block_v"], mem_lens=lens_d)
    memg, projg, maskg = _gather_pages(mem_pool, proj_pool, mask_pool, table)
    c_d, tok_d, lp_d = fused_decode_stride(
        cell, carry, token, finished, memg, projg, maskg, noise,
        jnp.int32(0), jnp.int32(n), **kw,
    )
    c_p, tok_p, lp_p = fused_decode_stride_paged(
        cell, carry, token, finished, mem_pool, proj_pool, mask_pool,
        table, noise, jnp.int32(0), jnp.int32(n), **kw,
    )
    np.testing.assert_array_equal(np.asarray(tok_p), np.asarray(tok_d))
    np.testing.assert_array_equal(np.asarray(lp_p), np.asarray(lp_d))
    for a, b in zip(jax.tree.leaves(c_p), jax.tree.leaves(c_d)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_paged_stride_validates_operands():
    """Malformed paged operands fail loudly at the wrapper, not deep in
    lowering: a 3-D page table, a 2-D mem pool, and a noise block whose lane
    axis disagrees with G are each rejected."""
    from cst_captioning_tpu.ops.decode_pallas import fused_decode_stride_paged

    dims = DIMS["small-2layer"]
    model, params, enc, carry, token = _setup(dims, "float32")
    cell = params["params"]["cell"]
    G, B = token.shape
    M = enc.memory.shape[1]
    S, V = 2, dims["V"]
    lens = np.full((B,), M, np.int32)
    mem_pool, proj_pool, mask_pool, table = _page_scatter(
        enc, lens, 3, -(-M // 3), B * -(-M // 3) + 2
    )
    finished = jnp.zeros((G, B), bool)
    noise = jnp.zeros((S, G - 1, B, V), jnp.float32)
    kw = dict(steps=S, num_layers=dims["L"])
    with pytest.raises(ValueError, match="page_table"):
        fused_decode_stride_paged(
            cell, carry, token, finished, mem_pool, proj_pool, mask_pool,
            table[None], noise, jnp.int32(0), **kw,
        )
    with pytest.raises(ValueError, match="pool"):
        fused_decode_stride_paged(
            cell, carry, token, finished, mem_pool[:, :, 0], proj_pool,
            mask_pool, table, noise, jnp.int32(0), **kw,
        )
    with pytest.raises(ValueError, match="noise"):
        fused_decode_stride_paged(
            cell, carry, token, finished, mem_pool, proj_pool, mask_pool,
            table, noise[:, :1, :1], jnp.int32(0), **kw,
        )


# ---------------------------------------------------------------------------
# fused beam step (decode + in-kernel top-W candidate selection)
# ---------------------------------------------------------------------------


# tier-1 keeps the full (t, min_len) regime sweep on "small" plus one
# multi-layer case; the rest of the dims product is slow-marked — every
# combo is a fresh interpret-mode kernel trace and the sweep is
# compile-bound, not assertion-bound
_BEAM_KERNEL_CASES = [
    pytest.param(name, t, ml, marks=()
                 if name == "small" or (name, t, ml) ==
                 ("small-2layer", 1, 3)
                 else pytest.mark.slow)
    for name in sorted(DIMS) for (t, ml) in [(0, 0), (1, 3), (4, 3)]
]


@pytest.mark.parametrize("name,t,min_len", _BEAM_KERNEL_CASES)
def test_beam_kernel_matches_composite(name, t, min_len):
    """The beam-step kernel vs its plain-jnp composite
    (``_reference_beam_topk``) over the dims sweep and the min_len
    regimes: the selected flat candidate ids are EXACT (selection happens
    on raw per-lane logits, monotone under the per-lane logsumexp shift)
    and scores/carry agree to kernel-vs-XLA float tolerance."""
    from cst_captioning_tpu.ops.decode_pallas import (
        _reference_beam_topk, fused_beam_step,
    )

    dims = DIMS[name]
    W = 4
    model, params, enc, _, _ = _setup(dims, "float32", K=W - 1)
    cell = params["params"]["cell"]
    B = dims["B"]
    rng = np.random.default_rng(3 + t)
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape)
        + jnp.asarray(rng.normal(scale=0.01, size=(W,) + x.shape),
                      jnp.float32),
        enc.carry,
    )
    token = jnp.asarray(rng.integers(1, dims["V"], size=(W, B)), jnp.int32)
    finished = jnp.asarray(rng.random(size=(W, B)) < 0.3)
    scores = jnp.asarray(rng.normal(scale=2.0, size=(W, B)), jnp.float32)

    kw = dict(t=jnp.int32(t), min_len=min_len)
    carry_p, sc_p, fl_p = fused_beam_step(
        cell, carry, token, finished, scores, enc.memory, enc.memory_proj,
        enc.memory_mask, block_b=dims["block_b"], block_v=dims["block_v"],
        **kw,
    )
    carry_r, sc_r, fl_r = _reference_beam_topk(
        cell, carry, token, finished, scores, enc.memory, enc.memory_proj,
        enc.memory_mask, **kw,
    )
    np.testing.assert_array_equal(np.asarray(fl_p), np.asarray(fl_r))
    np.testing.assert_allclose(
        np.asarray(sc_p), np.asarray(sc_r), rtol=1e-5, atol=1e-5
    )
    for a, b in zip(jax.tree.leaves(carry_p), jax.tree.leaves(carry_r)):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=2e-6, atol=2e-6
        )


def test_beam_search_pallas_matches_reference_end_to_end():
    """Whole-search parity: ``beam_search`` with ``decode_impl="pallas"``
    (lane-batched over the beam kernel) returns the XLA reference beam's
    exact tokens at f32, with scores at kernel float tolerance — the
    stride-kernel convention (tokens exact, floats allclose) extended to
    beam."""
    from cst_captioning_tpu.decoding import beam_search

    dims = DIMS["small"]
    model, params, *_ = _setup(dims, "float32")
    m_pal = CaptionModel(dataclasses.replace(model.cfg, decode_impl="pallas"))
    rng = np.random.default_rng(0)
    feats = {"resnet": jnp.asarray(
        rng.normal(size=(dims["B"], dims["F"], 16)), jnp.float32
    )}
    masks = {"resnet": jnp.ones((dims["B"], dims["F"]), jnp.float32)}
    # W=1 (degenerate beam) is covered by the XLA-side lanes-vs-reference
    # pin; here each width is a fresh kernel trace, so sweep 3 and the
    # acceptance width 5
    for W in (3, 5):
        ref_tok, ref_sc = beam_search(
            model, params, feats, masks, beam_size=W, min_len=2,
            beam_impl="reference",
        )
        pal_tok, pal_sc = beam_search(
            m_pal, params, feats, masks, beam_size=W, min_len=2,
            beam_impl="lanes",
        )
        np.testing.assert_array_equal(
            np.asarray(pal_tok), np.asarray(ref_tok)
        )
        np.testing.assert_allclose(
            np.asarray(pal_sc), np.asarray(ref_sc), rtol=1e-5, atol=1e-5
        )


def test_beam_kernel_width_validation():
    """W > V cannot fill a lane's candidate list losslessly — rejected."""
    from cst_captioning_tpu.ops.decode_pallas import fused_beam_step

    dims = DIMS["small"]
    _, params, enc, _, _ = _setup(dims, "float32")
    cell = params["params"]["cell"]
    W, B = dims["V"] + 1, dims["B"]
    carry = jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (W,) + x.shape), enc.carry
    )
    token = jnp.ones((W, B), jnp.int32)
    with pytest.raises(ValueError, match="beam width"):
        fused_beam_step(
            cell, carry, token, jnp.zeros((W, B), bool),
            jnp.zeros((W, B), jnp.float32), enc.memory, enc.memory_proj,
            enc.memory_mask, t=jnp.int32(0),
        )
