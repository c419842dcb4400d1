"""Decoding tests: greedy, sampling, fused one-loop, beam — incl. oracles."""

import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID, ModelConfig
from cst_captioning_tpu.decoding import (
    beam_search,
    fused_decode,
    greedy_decode,
    sample_decode,
)
from cst_captioning_tpu.decoding.common import (
    _cdf_block,
    _first_over,
    apply_min_len,
    forbid_special,
    inverse_cdf_index,
    rollout_step_keys,
    sample_lanes,
    selected_logprob,
)
from cst_captioning_tpu.models import CaptionModel
from cst_captioning_tpu.models.captioner import CaptionModel as CM

from _gumbel_sample import gumbel_sample_decode

B, F, T, V = 4, 5, 6, 11


@pytest.fixture(scope="module")
def setup():
    cfg = ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 8),),
        d_embed=12,
        d_hidden=12,
        d_att=6,
        encoder="temporal_attention",
        max_len=T,
        max_frames=F,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(0)
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 8)), jnp.float32)}
    masks = {"resnet": jnp.ones((B, F), jnp.float32)}
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    params = model.init(jax.random.key(0), feats, masks, labels)
    return model, params, feats, masks


def _check_pad_after_eos(tokens):
    tokens = np.asarray(tokens)
    for row in tokens.reshape(-1, tokens.shape[-1]):
        seen_eos = False
        for t in row:
            if seen_eos:
                assert t == PAD_ID
            if t == EOS_ID:
                seen_eos = True


def test_greedy_shapes_and_padding(setup):
    model, params, feats, masks = setup
    tokens, logprobs = greedy_decode(model, params, feats, masks)
    assert tokens.shape == (B, T) and logprobs.shape == (B, T)
    _check_pad_after_eos(tokens)
    # PAD positions have zero logprob
    assert np.all(np.asarray(logprobs)[np.asarray(tokens) == PAD_ID] == 0.0)


def test_greedy_matches_manual_argmax(setup):
    model, params, feats, masks = setup
    tokens, _ = greedy_decode(model, params, feats, masks)
    enc = model.apply(params, feats, masks, method=CM.encode)
    carry, tok = enc.carry, jnp.full((B,), BOS_ID, jnp.int32)
    manual = []
    finished = np.zeros(B, bool)
    for _ in range(T):
        carry, logits = model.apply(params, carry, tok, enc, method=CM.decode_step)
        nxt = np.asarray(jnp.argmax(forbid_special(logits), -1)).astype(np.int32)
        nxt[finished] = PAD_ID
        finished |= nxt == EOS_ID
        manual.append(nxt)
        tok = jnp.asarray(nxt)
    np.testing.assert_array_equal(tokens, np.stack(manual, 1))


def test_sample_rollouts_reproducible_and_distinct(setup):
    model, params, feats, masks = setup
    rng = jax.random.key(42)
    t1, lp1 = sample_decode(model, params, feats, masks, rng, num_rollouts=3)
    t2, lp2 = sample_decode(model, params, feats, masks, rng, num_rollouts=3)
    assert t1.shape == (3, B, T)
    np.testing.assert_array_equal(t1, t2)  # same key -> identical
    # different rollouts differ somewhere (tiny chance of collision)
    assert not np.array_equal(np.asarray(t1[0]), np.asarray(t1[1]))
    _check_pad_after_eos(t1)
    assert np.all(np.asarray(lp1)[np.asarray(t1) == PAD_ID] == 0.0)
    # sampled-token logprobs are real logprobs (negative where not PAD)
    assert np.all(np.asarray(lp1)[np.asarray(t1) != PAD_ID] < 0.0)


def test_sample_temperature_zero_limit(setup):
    """Very low temperature sampling ≈ greedy decoding."""
    model, params, feats, masks = setup
    tg, _ = greedy_decode(model, params, feats, masks)
    ts, _ = sample_decode(
        model, params, feats, masks, jax.random.key(0), num_rollouts=1,
        temperature=1e-4,
    )
    np.testing.assert_array_equal(tg, ts[0])


def test_beam1_equals_greedy(setup):
    model, params, feats, masks = setup
    tg, _ = greedy_decode(model, params, feats, masks)
    tb, _ = beam_search(model, params, feats, masks, beam_size=1)
    np.testing.assert_array_equal(tg, tb)


def test_beam_search_improves_or_matches_score(setup):
    """Beam-5 total logprob >= greedy total logprob for every sequence."""
    model, params, feats, masks = setup

    def seq_logprob(tokens_row):
        """Total model logprob of a fixed token row, teacher-forced."""
        labels = tokens_row[None, :]
        # score through model __call__ on a single row
        f1 = {k: v[:1] for k, v in feats.items()}
        m1 = {k: v[:1] for k, v in masks.items()}
        logits = forbid_special(model.apply(params, f1, m1, labels))
        logp = jax.nn.log_softmax(logits, -1)
        lp = jnp.take_along_axis(logp, labels[..., None], -1)[..., 0]
        mask = (labels != PAD_ID).astype(jnp.float32)
        return float((lp * mask).sum())

    tg, _ = greedy_decode(model, params, feats, masks)
    tb, scores = beam_search(model, params, feats, masks, beam_size=5)
    # row 0 only (seq_logprob uses feats[0:1])
    assert seq_logprob(tb[0]) >= seq_logprob(tg[0]) - 1e-4


def test_beam_matches_bruteforce_oracle(setup):
    """Beam=V on a tiny space == exhaustive enumeration of all sequences."""
    model, params, feats, masks = setup
    Tshort = 3
    f1 = {k: v[:1] for k, v in feats.items()}
    m1 = {k: v[:1] for k, v in masks.items()}

    # enumerate canonical sequences (nothing after first EOS), then score
    # them ALL in one batched teacher-forced pass instead of ~2k step calls
    alphabet = list(range(2, V))  # EOS and real words (skip PAD, BOS)
    candidates = []
    for seq in itertools.product(alphabet, repeat=Tshort):
        if EOS_ID in seq:
            k = seq.index(EOS_ID)
            if any(s != EOS_ID for s in seq[k + 1 :]):
                continue  # duplicate of the truncated form
        candidates.append(seq)
    cand = np.asarray(candidates, np.int32)                     # [N, Tshort]
    N = cand.shape[0]
    fN = {k: jnp.broadcast_to(v[:1], (N,) + v.shape[1:]) for k, v in f1.items()}
    mN = {k: jnp.broadcast_to(v[:1], (N,) + v.shape[1:]) for k, v in m1.items()}
    logits = forbid_special(model.apply(params, fN, mN, jnp.asarray(cand)))
    logp = np.asarray(jax.nn.log_softmax(logits, -1))
    tok_lp = np.take_along_axis(logp, cand[..., None], -1)[..., 0]  # [N, T]
    # mask: count tokens up to and including first EOS
    scores_all = np.zeros(N)
    for i, seq in enumerate(candidates):
        L = seq.index(EOS_ID) + 1 if EOS_ID in seq else Tshort
        scores_all[i] = tok_lp[i, :L].sum()
    best = int(np.argmax(scores_all))
    best_score, best_seq = scores_all[best], candidates[best]

    tb, scores = beam_search(
        model, params, f1, m1, beam_size=(V - 2) ** 2, max_len=Tshort
    )
    got = [t for t in np.asarray(tb)[0].tolist() if t != PAD_ID]
    want = list(best_seq[: best_seq.index(EOS_ID) + 1] if EOS_ID in best_seq else best_seq)
    assert got == want, f"beam {got} vs oracle {want}"
    np.testing.assert_allclose(float(scores[0]), best_score, rtol=1e-4)


def test_beam_return_all_sorted(setup):
    model, params, feats, masks = setup
    tokens, scores = beam_search(
        model, params, feats, masks, beam_size=4, return_all=True
    )
    assert tokens.shape == (B, 4, T) and scores.shape == (B, 4)
    s = np.asarray(scores)
    assert np.all(np.diff(s, axis=1) <= 1e-6)  # descending


def test_selected_logprob_matches_log_softmax():
    """The one-pass selected-row logprob (logit - logsumexp) equals the
    full log_softmax + gather it replaced, across shapes and dtypes."""
    rng = np.random.default_rng(7)
    for shape in [(4, 11), (3, 4, 11), (2, 3, 4, 7)]:
        logits = jnp.asarray(rng.normal(size=shape) * 5, jnp.float32)
        token = jnp.asarray(rng.integers(0, shape[-1], size=shape[:-1]), jnp.int32)
        want = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), token[..., None], axis=-1
        )[..., 0]
        got = selected_logprob(logits, token)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
        )


def test_rollout_step_keys_is_the_fold_in_chain():
    """The precomputed [T, K] key array is EXACTLY fold_in(fold_in(rng, k),
    t) — the per-step re-fold it replaced, bit-for-bit (satellite of the
    decode fast path: same sampling streams by construction)."""
    rng = jax.random.key(123)
    K, T = 4, 7
    keys = rollout_step_keys(rng, K, T)
    assert keys.shape == (T, K)
    got = jax.random.key_data(keys)
    for t in range(T):
        for k in range(K):
            want = jax.random.key_data(
                jax.random.fold_in(jax.random.fold_in(rng, k), t)
            )
            np.testing.assert_array_equal(np.asarray(got[t, k]), np.asarray(want))


def test_sample_matches_manual_per_step_folding(setup):
    """sample_decode (precomputed key array) decodes bit-identical tokens to
    a manual loop that re-folds the K keys inside every step body and takes
    each lane's token by the inverse CDF of its one uniform."""
    model, params, feats, masks = setup
    K = 3
    rng = jax.random.key(5)
    tokens, _ = sample_decode(model, params, feats, masks, rng, num_rollouts=K)

    enc = model.apply(params, feats, masks, method=CM.encode)
    keys = jax.vmap(lambda k: jax.random.fold_in(rng, k))(jnp.arange(K))
    carry = jax.tree.map(lambda x: jnp.broadcast_to(x[None], (K,) + x.shape), enc.carry)
    tok = jnp.full((K, B), BOS_ID, jnp.int32)
    finished = np.zeros((K, B), bool)
    manual = []
    for t in range(T):
        carry, logits = jax.vmap(
            lambda c, t_: model.apply(params, c, t_, enc, method=CM.decode_step)
        )(carry, tok)
        logits = forbid_special(logits)
        step_keys = jax.vmap(lambda k_: jax.random.fold_in(k_, t))(keys)
        u = jax.vmap(
            lambda k_: jax.random.uniform(k_, (B,), logits.dtype)
        )(step_keys)
        nxt = np.array(inverse_cdf_index(logits, u), np.int32)
        nxt[finished] = PAD_ID
        finished |= nxt == EOS_ID
        manual.append(nxt)
        tok = jnp.asarray(nxt)
    np.testing.assert_array_equal(np.asarray(tokens), np.stack(manual, -1))


# ---- the inverse-CDF selection (common.inverse_cdf_index) --------------------

def _skewed_logits(V, rows=1, seed=0):
    """[rows, V] logits with a few heavy columns and a long thin tail."""
    r = np.random.default_rng(seed)
    return jnp.asarray(
        r.normal(size=(rows, V)) * 2.0 + np.linspace(3.0, -3.0, V), jnp.float32
    )


def _cdf_reference(tl, u):
    """The same selection in float64 on the host: first column whose
    cumulative probability passes u."""
    p = np.exp(np.asarray(tl, np.float64))
    cdf = np.cumsum(p, -1)
    target = np.asarray(u, np.float64) * cdf[..., -1]
    return (cdf <= target[..., None]).sum(-1)


@pytest.mark.parametrize("temperature", [1.0, 0.7])
def test_inverse_cdf_frequencies_match_softmax(temperature):
    """Some 2e5 draws through ``sample_lanes`` (K keys, one uniform a row)
    land on each column as often as softmax(masked logits / temperature)
    says: chi-square over the columns that expect at least 5 draws, and
    never a masked column. V = 200 is 5 blocks of 40."""
    V, K, rows = 200, 4, 50_000
    assert _cdf_block(V) == 40
    logits = apply_min_len(forbid_special(_skewed_logits(V)), 0, 1)
    tl = jnp.broadcast_to(logits / temperature, (K, rows, V))
    keys = jax.random.split(jax.random.key(7), K)
    got = np.asarray(jax.jit(sample_lanes)(keys, tl)).ravel()
    counts = np.bincount(got, minlength=V)
    assert counts[[PAD_ID, BOS_ID, EOS_ID]].sum() == 0
    want = np.asarray(jax.nn.softmax(logits[0] / temperature), np.float64) * got.size
    seen = want >= 5.0
    chi2 = ((counts[seen] - want[seen]) ** 2 / want[seen]).sum()
    df = int(seen.sum()) - 1
    assert chi2 < df + 5.0 * np.sqrt(2.0 * df), (chi2, df)
    assert counts[~seen].sum() <= 3.0 * want[~seen].sum() + 10


@pytest.mark.parametrize("V", [200, 300, 11])
def test_inverse_cdf_never_emits_a_masked_column(V):
    """Over a grid of u that holds 0.0 and the largest float under 1.0, with
    PAD/BOS forbidden and ``min_len`` holding EOS back, no row of any skew
    ever takes a masked column or one past V (300 = 2 x 128 + 44: the last
    block is padded; 11 is one block)."""
    u = jnp.asarray(np.concatenate([
        [0.0, np.nextafter(np.float32(1.0), np.float32(0.0))],
        np.linspace(0.0, 1.0, 255, endpoint=False),
    ]), jnp.float32)
    logits = _skewed_logits(V, rows=6, seed=V) * jnp.asarray(
        [[0.1], [1.0], [1.0], [5.0], [20.0], [60.0]], jnp.float32)
    tl = apply_min_len(forbid_special(logits), 0, 3)
    got = np.asarray(inverse_cdf_index(
        jnp.broadcast_to(tl[:, None], (6, u.size, V)),
        jnp.broadcast_to(u, (6, u.size)),
    ))
    assert got.min() >= 0 and got.max() < V
    assert not np.isin(got, [PAD_ID, BOS_ID, EOS_ID]).any()
    # the column taken has mass in f32, and u = 0 takes the first that has
    tl64 = np.asarray(tl, np.float64)
    e = np.exp(tl64 - tl64.max(-1, keepdims=True))
    assert (np.take_along_axis(e.astype(np.float32), got, -1) > 0).all()
    for row, first in zip(e, got[:, 0]):
        assert (row[:first] < 1e-37).all()


def test_inverse_cdf_clamps_to_the_last_column_with_mass():
    """Where no cumulative sum passes the target (rounding between a block's
    sum and the sum inside it), the row takes the last term WITH mass, never
    a zero term behind it and never one past the end; end to end, a row
    whose mass sits in the first column of the last block, or in the last
    column of a V that does not fill its last block, lands there for every u."""
    mass = jnp.asarray([[0.5, 0.5, 0.0, 0.0], [0.0, 0.25, 0.0, 0.75]], jnp.float32)
    cum = jnp.cumsum(mass, -1)
    # a prefix sum that lost monotonicity on a zero term must not win it
    cum = cum.at[0, 2].set(1.5)
    np.testing.assert_array_equal(
        np.asarray(_first_over(mass, cum, jnp.asarray([1.0, 1.0]))), [1, 3])
    np.testing.assert_array_equal(
        np.asarray(_first_over(mass, cum, jnp.asarray([0.0, 0.1]))), [0, 1])

    V = 300
    assert _cdf_block(V) == 128
    u = jnp.asarray([0.0, 0.3, 0.999, np.nextafter(np.float32(1), np.float32(0))])
    for col in (256, V - 1):
        tl = jnp.full((V,), -1.0e9, jnp.float32).at[col].set(2.0)
        got = inverse_cdf_index(jnp.broadcast_to(tl, (u.size, V)), u)
        np.testing.assert_array_equal(np.asarray(got), [col] * u.size)


def test_inverse_cdf_hand_checked_on_both_sides_of_a_block_edge():
    """Uniform logits over V = 256 (two blocks of 128, every sum exact in
    f32): u = 1/2 is the first column of the second block, the float under
    it the last of the first; and a skewed row agrees with the float64 CDF
    wherever u is not within rounding of a column's edge."""
    V = 256
    assert _cdf_block(V) == 128
    half = np.float32(0.5)
    u = jnp.asarray([0.0, 127.5 / 256, np.nextafter(half, np.float32(0)),
                     half, 128.5 / 256, 255.5 / 256], jnp.float32)
    got = inverse_cdf_index(jnp.zeros((u.size, V), jnp.float32), u)
    np.testing.assert_array_equal(np.asarray(got), [0, 127, 127, 128, 128, 255])

    V = 200
    tl = jax.nn.log_softmax(_skewed_logits(V, rows=1, seed=3)[0])
    u = jnp.asarray(np.random.default_rng(4).uniform(size=4096), jnp.float32)
    want = _cdf_reference(jnp.broadcast_to(tl, (u.size, V)), u)
    got = np.asarray(inverse_cdf_index(jnp.broadcast_to(tl, (u.size, V)), u))
    cdf = np.cumsum(np.exp(np.asarray(tl, np.float64)))
    near_edge = np.abs(cdf[None, :] - np.asarray(u, np.float64)[:, None]).min(-1) < 1e-5
    np.testing.assert_array_equal(got[~near_edge], want[~near_edge])
    assert (np.abs(got - want) <= 1).all()


def test_inverse_cdf_is_rowwise_and_jit_stable():
    """Bit-identical under jit, and a batch equals its two halves selected
    apart: a row's token depends on its own logits and uniform alone."""
    V = 300
    tl = forbid_special(_skewed_logits(V, rows=3 * 8, seed=5).reshape(3, 8, V))
    u = jax.random.uniform(jax.random.key(1), (3, 8))
    whole = np.asarray(inverse_cdf_index(tl, u))
    np.testing.assert_array_equal(np.asarray(jax.jit(inverse_cdf_index)(tl, u)), whole)
    halves = np.concatenate([
        np.asarray(inverse_cdf_index(tl[:, :4], u[:, :4])),
        np.asarray(inverse_cdf_index(tl[:, 4:], u[:, 4:])),
    ], axis=1)
    np.testing.assert_array_equal(halves, whole)


def test_fused_decode_matches_two_loop_bitexact(setup):
    """The fused one-loop decode is BIT-EXACT against the two-loop reference
    under a fixed rng: greedy tokens/logprobs (lane 0 vs greedy_decode) and
    sampled tokens/logprobs (lanes 1..K vs the Gumbel-max loop the family
    shares its stream with, tests/_gumbel_sample.py)."""
    model, params, feats, masks = setup
    K = 3
    rng = jax.random.key(42)
    tg, lg = greedy_decode(model, params, feats, masks)
    ts, ls = gumbel_sample_decode(model, params, feats, masks, rng, num_rollouts=K)
    fg, flg, fs, fls = fused_decode(
        model, params, feats, masks, rng, num_rollouts=K
    )
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(tg))
    np.testing.assert_array_equal(np.asarray(flg), np.asarray(lg))
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(ts))
    np.testing.assert_array_equal(np.asarray(fls), np.asarray(ls))
    # and under jit, exactly as make_rl_decode dispatches it
    fg2, _, fs2, _ = jax.jit(
        lambda p, f, m, r: fused_decode(model, p, f, m, r, num_rollouts=K)
    )(params, feats, masks, rng)
    np.testing.assert_array_equal(np.asarray(fg2), np.asarray(tg))
    np.testing.assert_array_equal(np.asarray(fs2), np.asarray(ts))


def test_fused_decode_temperature_and_padding(setup):
    """Temperature reaches the sampled lanes only (greedy lane untempered),
    and every lane honors PAD-after-EOS / zero-logprob padding."""
    model, params, feats, masks = setup
    rng = jax.random.key(3)
    ts, _ = gumbel_sample_decode(
        model, params, feats, masks, rng, num_rollouts=2, temperature=0.5
    )
    fg, flg, fs, fls = fused_decode(
        model, params, feats, masks, rng, num_rollouts=2, temperature=0.5
    )
    tg, _ = greedy_decode(model, params, feats, masks)
    np.testing.assert_array_equal(np.asarray(fs), np.asarray(ts))
    np.testing.assert_array_equal(np.asarray(fg), np.asarray(tg))
    _check_pad_after_eos(fg)
    _check_pad_after_eos(fs)
    assert np.all(np.asarray(fls)[np.asarray(fs) == PAD_ID] == 0.0)
    assert np.all(np.asarray(flg)[np.asarray(fg) == PAD_ID] == 0.0)


def test_min_len_suppresses_early_eos(setup):
    model, params, feats, masks = setup
    tg, _ = greedy_decode(model, params, feats, masks, min_len=3)
    tb, _ = beam_search(model, params, feats, masks, beam_size=3, min_len=3)
    for tokens in (np.asarray(tg), np.asarray(tb)):
        lengths = (tokens != PAD_ID).sum(axis=1)
        assert (lengths >= 3).all(), tokens
        assert not (tokens[:, :2] == EOS_ID).any()


# ---- stride + compaction (decode endgame) -----------------------------------

def test_gumbel_step_noise_is_categorical_bitwise():
    """The Gumbel-max spelling (noise precomputed via gumbel_step_noise,
    argmax outside) is BIT-IDENTICAL to the vmapped jax.random.categorical
    it replaced — the invariant that lets the fused stride paths (and the
    in-kernel selection) reuse the exact sample_decode RNG streams."""
    from cst_captioning_tpu.decoding.common import gumbel_step_noise

    key = jax.random.key(9)
    keys = jax.vmap(lambda k: jax.random.fold_in(key, k))(jnp.arange(4))
    logits = jnp.asarray(
        np.random.default_rng(3).normal(size=(4, 7, 13)) * 5, jnp.float32
    )
    for temp in (1.0, 0.7):
        want = jax.vmap(
            lambda k_, l_: jax.random.categorical(k_, l_ / temp, axis=-1)
        )(keys, logits)
        tl = logits / temp
        noise = gumbel_step_noise(keys, tl.shape[1:], tl.dtype)
        got = jnp.argmax(tl + noise, axis=-1)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.fixture(scope="module")
def eos_setup():
    """Like ``setup`` but with the EOS logit nudged up so lanes finish at
    varied steps — random EOS patterns are what compaction must survive."""
    cfg = ModelConfig(
        vocab_size=V,
        modalities=(("resnet", 8),),
        d_embed=12,
        d_hidden=12,
        d_att=6,
        encoder="temporal_attention",
        max_len=T,
        max_frames=F,
        dtype="float32",
    )
    model = CaptionModel(cfg)
    rng = np.random.default_rng(7)
    feats = {"resnet": jnp.asarray(rng.normal(size=(B, F, 8)), jnp.float32)}
    masks = {"resnet": jnp.ones((B, F), jnp.float32)}
    labels = jnp.asarray(rng.integers(4, V, size=(B, T)), jnp.int32)
    params = model.init(jax.random.key(1), feats, masks, labels)
    bias = params["params"]["cell"]["out_proj"]["bias"]
    params["params"]["cell"]["out_proj"]["bias"] = bias.at[EOS_ID].add(1.5)
    return model, params, feats, masks


def test_fused_stride_compaction_token_and_logprob_exact(eos_setup):
    """EVERY (stride, compact) combination is bit-equal — tokens AND
    logprobs, greedy AND sampled lanes — to the stride-1 uncompacted loop
    under a fixed rng, across random EOS patterns (lanes finish at varied
    steps, so the compaction permutation is exercised for real). Covers the
    stride-boundary case (S=4 not dividing T=6) and S > T clamping."""
    model, params, feats, masks = eos_setup
    rng = jax.random.key(42)
    K = 3
    ref = fused_decode(
        model, params, feats, masks, rng, num_rollouts=K,
        decode_stride=1, compact=False,
    )
    # sanity: the EOS nudge produced genuinely ragged finishes
    lens = (np.asarray(ref[2]) != PAD_ID).sum(-1)
    assert lens.min() < T or lens.max() == T
    # stride 1 + compact normalizes to the plain loop (fused_decode), so
    # the compacted combinations all have S >= 2
    for stride, compact in [(1, True), (2, True), (3, True), (4, True),
                            (4, False), (6, True), (16, True), (8, True)]:
        got = fused_decode(
            model, params, feats, masks, rng, num_rollouts=K,
            decode_stride=stride, compact=compact,
        )
        for a, b, what in zip(got, ref, ("g", "glp", "s", "slp")):
            np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b),
                err_msg=f"stride={stride} compact={compact} {what}",
            )


def test_fused_stride_default_knobs_from_config(eos_setup):
    """fused_decode reads decode_stride / decode_compact off the model
    config when not overridden — and the config defaults (stride 8,
    compaction on) stay bit-exact vs the explicit stride-1 call."""
    import dataclasses

    model, params, feats, masks = eos_setup
    assert model.cfg.decode_stride == 8 and model.cfg.decode_compact
    rng = jax.random.key(5)
    ref = fused_decode(
        model, params, feats, masks, rng, num_rollouts=2,
        decode_stride=1, compact=False,
    )
    by_default = fused_decode(
        model, params, feats, masks, rng, num_rollouts=2
    )
    m2 = CaptionModel(
        dataclasses.replace(model.cfg, decode_stride=3, decode_compact=False)
    )
    by_cfg = fused_decode(m2, params, feats, masks, rng, num_rollouts=2)
    for got in (by_default, by_cfg):
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_fused_stride_under_jit_and_temperature(eos_setup):
    """The strided+compacted loop jits (one compiled program, traced
    while loop) and keeps temperature semantics: sampled lanes tempered,
    greedy lane untempered — still bit-equal to the stride-1 loop."""
    model, params, feats, masks = eos_setup
    rng = jax.random.key(12)
    ref = fused_decode(
        model, params, feats, masks, rng, num_rollouts=2, temperature=0.6,
        decode_stride=1, compact=False,
    )
    got = jax.jit(
        lambda p, f, m, r: fused_decode(
            model, p, f, m, r, num_rollouts=2, temperature=0.6,
            decode_stride=4, compact=True,
        )
    )(params, feats, masks, rng)
    for a, b in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _check_pad_after_eos(got[0])
    _check_pad_after_eos(got[2])


def test_decode_stride_config_validation():
    with pytest.raises(ValueError, match="decode_stride"):
        ModelConfig(decode_stride=0)


# ---------------------------------------------------------------------------
# lane-batched beam (decoding/beam.py beam_impl="lanes") vs the sequential
# reference — the bit-parity contract the eval fast path rests on
# ---------------------------------------------------------------------------


def test_beam_lanes_matches_reference_bit_exact(setup, eos_setup):
    """The lane-batched beam is token- AND score-BIT-exact vs the kept
    ``beam_impl="reference"`` oracle at f32 — same per-lane float programs
    (vmap over lanes vs flat [B*W] batch), same ``row_logprobs`` spelling,
    same flattened ``top_k`` — across beam widths, both EOS regimes (the
    eos_setup rows finish raggedly), and an S-indivisible horizon
    (max_len=11 exercises the scan boundary T % stride != 0). The tier-1
    sweep pins the acceptance width (W=5) on both fixtures and spends
    the ragged-EOS fixture on the remaining axes (scan boundary at 5 and
    3, the W=1 degenerate beam); the full W x T x fixture product rides
    the slow-marked exhaustive twin below — each combo is a fresh scan
    compile, and the product is compile-bound, not assertion-bound."""
    for fix, combos in (
        (setup, ((5, T), (3, T))),
        (eos_setup, ((5, T), (5, 11), (3, 11), (1, T))),
    ):
        model, params, feats, masks = fix
        for W, max_len in combos:
            ref_tok, ref_sc = beam_search(
                model, params, feats, masks, beam_size=W, max_len=max_len,
                beam_impl="reference",
            )
            lane_tok, lane_sc = beam_search(
                model, params, feats, masks, beam_size=W, max_len=max_len,
                beam_impl="lanes",
            )
            np.testing.assert_array_equal(
                np.asarray(lane_tok), np.asarray(ref_tok)
            )
            assert np.asarray(lane_sc).tobytes() == np.asarray(
                ref_sc
            ).tobytes(), f"scores not bit-equal at W={W} T={max_len}"


@pytest.mark.slow
def test_beam_lanes_matches_reference_exhaustive(setup, eos_setup):
    """The full W x max_len x fixture product of the bit-parity pin
    above (slow: 24 scan compiles)."""
    for fix in (setup, eos_setup):
        model, params, feats, masks = fix
        for W, max_len in itertools.product((1, 3, 5), (T, 11)):
            ref_tok, ref_sc = beam_search(
                model, params, feats, masks, beam_size=W, max_len=max_len,
                beam_impl="reference",
            )
            lane_tok, lane_sc = beam_search(
                model, params, feats, masks, beam_size=W, max_len=max_len,
                beam_impl="lanes",
            )
            np.testing.assert_array_equal(
                np.asarray(lane_tok), np.asarray(ref_tok)
            )
            assert np.asarray(lane_sc).tobytes() == np.asarray(
                ref_sc
            ).tobytes(), f"scores not bit-equal at W={W} T={max_len}"


def test_beam_lanes_return_all_matches_reference(eos_setup):
    """``return_all`` surfaces the same W ranked hypotheses from both
    implementations (tokens exact, scores bit-equal) — the lane layout
    transpose back to [B, W, T] loses nothing."""
    model, params, feats, masks = eos_setup
    ref_tok, ref_sc = beam_search(
        model, params, feats, masks, beam_size=4, return_all=True,
        beam_impl="reference",
    )
    lane_tok, lane_sc = beam_search(
        model, params, feats, masks, beam_size=4, return_all=True,
        beam_impl="lanes",
    )
    np.testing.assert_array_equal(np.asarray(lane_tok), np.asarray(ref_tok))
    assert np.asarray(lane_sc).tobytes() == np.asarray(ref_sc).tobytes()


def test_beam_impl_validation():
    with pytest.raises(ValueError, match="beam_impl"):
        beam_search(None, None, None, None, beam_impl="bogus")


def test_npad_anytime_answer_is_monotone_vs_greedy(setup, eos_setup):
    """NPAD's best-sum-logprob lane is >= greedy by construction: lane 0
    IS the greedy rollout and argmax over lane sums can only improve on
    it (arXiv 1605.03835's anytime property). Pinned on both EOS regimes,
    one noise temperature each (below and above 1 — each temperature is
    a fresh rollout compile)."""
    from cst_captioning_tpu.decoding import npad_decode

    for fix, temps in ((setup, (0.7,)), (eos_setup, (1.3,))):
        model, params, feats, masks = fix
        _, g_lp = greedy_decode(model, params, feats, masks)
        g_sum = np.asarray(g_lp.sum(axis=-1))
        for temperature in temps:
            tok, sc = npad_decode(
                model, params, feats, masks, jax.random.key(3),
                num_lanes=4, temperature=temperature,
            )
            assert tok.shape[0] == B and np.asarray(sc).shape == (B,)
            assert np.all(np.asarray(sc) >= g_sum - 1e-6), (
                f"NPAD worse than greedy at temperature={temperature}"
            )
            _check_pad_after_eos(tok)


def test_npad_low_temperature_collapses_to_greedy(setup):
    """In the temperature->0 limit every noisy lane decodes the greedy
    tokens (the ``test_sample_temperature_zero_limit`` contract), their
    recorded logprob sums coincide with the greedy lane's, and the argmax
    tie breaks to lane 0 — so NPAD returns exactly the greedy tokens and
    score. This is the tie-break contract ``npad_best_lane_index`` (and
    the >=-greedy guarantee) relies on."""
    from cst_captioning_tpu.decoding import npad_decode

    model, params, feats, masks = setup
    g_tok, g_lp = greedy_decode(model, params, feats, masks)
    tok, sc = npad_decode(
        model, params, feats, masks, jax.random.key(5), num_lanes=3,
        temperature=1e-4,
    )
    np.testing.assert_array_equal(np.asarray(tok), np.asarray(g_tok))
    np.testing.assert_array_equal(
        np.asarray(sc), np.asarray(g_lp.sum(axis=-1))
    )
