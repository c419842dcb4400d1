"""Plain reference of the attention-LSTM captioner (Phan et al.,
arXiv:1712.09532, as `cst_captioning` builds it): frame embedding, additive
temporal attention (one slot per modality when the encoder mean-pools), an
input-feed LSTM decoder, a vocabulary projection. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: one plain loop over
the caption's positions, no kernels, no cache, no batching tricks, written
from the equations and independent of the program's modules. It reads the
program's parameter tree (flax names) and the configuration's ``model`` dict
(``encoder`` and the modality names), and nothing else. Departures from the
paper: the LSTM carry is initialised from the masked mean of the memory
through two dense layers (the program's choice), and PAD/BOS are forbidden at
decode time (``forbid_special=True`` reproduces the decode loops'
distribution; teacher forcing leaves them in).

The one entry every training job calls, and every configuration's reference
module has, is :func:`token_logprobs`; it is differentiable in ``params``.
The ``eval`` job calls :func:`beam_logprobs`, the same walk read for what a
beam search may be held to, and :func:`beam_search`, the search itself.
``precision`` is the type the operands of every matrix product are rounded
to (the sums stay float32): ``float32`` is the reference; ``bfloat16`` and
``float8_e4m3fn`` (scaled to the tensor's largest magnitude) are the
controls that ``correct`` has been shown to refuse, and nothing else uses them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2


def rounder(precision: str):
    """x -> x rounded to ``precision`` (a one-byte type after scaling to the
    tensor's largest magnitude) and back to float32. The gradient passes
    straight through, so that a control reads the coarser products and not
    cotangents that underflow."""
    if precision == "float32":
        return lambda x: x
    dtype = jnp.dtype(precision)
    top = float(jnp.finfo(dtype).max)

    def rounded(x):
        x0 = jax.lax.stop_gradient(x)
        if dtype.itemsize > 1:
            y = x0.astype(dtype).astype(jnp.float32)
        else:
            s = jnp.maximum(jnp.max(jnp.abs(x0)), 1e-30) / top
            y = (x0 / s).astype(dtype).astype(jnp.float32) * s
        return x + (y - x0)

    return rounded


def _dense(p, x, r):
    y = r(x) @ r(p["kernel"])
    return y + p["bias"] if "bias" in p else y


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def encode(params, model: dict, feats, masks, r=rounder("float32")):
    """-> (memory [B, M, E], memory_proj [B, M, A], mask [B, M], (c0, h0))."""
    p = params["params"]
    banks, bmasks = [], []
    for name, _ in model["modalities"]:
        x = jnp.asarray(feats[name], jnp.float32)
        m = jnp.asarray(masks[name], jnp.float32)
        if model["encoder"] == "meanpool":
            x = (x * m[..., None]).sum(1) / jnp.maximum(m.sum(1), 1.0)[:, None]
            banks.append(jnp.tanh(_dense(p["encoder"][f"embed_{name}"], x, r))[:, None])
            bmasks.append(jnp.ones((x.shape[0], 1), jnp.float32))
        else:
            banks.append(jnp.tanh(_dense(p["encoder"][f"embed_{name}"], x, r)))
            bmasks.append(m)
    memory = jnp.concatenate(banks, axis=1)
    mask = jnp.concatenate(bmasks, axis=1)
    memory = memory * mask[..., None]
    proj = r(memory) @ r(p["cell"]["attention"]["mem_proj"]["kernel"])
    ctx0 = (memory * mask[..., None]).sum(1) / jnp.maximum(mask.sum(1), 1.0)[:, None]
    c0 = jnp.tanh(_dense(p["init_c0"], ctx0, r))
    h0 = jnp.tanh(_dense(p["init_h0"], ctx0, r))
    return memory, proj, mask, (c0, h0)


def _step(p, carry, token, memory, proj, mask, r):
    c, h = carry
    att = p["attention"]
    q = _dense(att["query_proj"], h, r)
    scores = (r(jnp.tanh(proj + q[:, None, :])) @ r(att["score"]["kernel"]))[..., 0]
    scores = jnp.where(mask > 0, scores, -1.0e9)
    ctx = jnp.einsum("bm,bme->be", r(jax.nn.softmax(scores, axis=-1)), r(memory))
    x = jnp.concatenate([p["word_embed"]["embedding"][token], ctx], axis=-1)
    lstm = p["lstm0"]
    gate = lambda g: _dense(lstm["i" + g], x, r) + _dense(lstm["h" + g], h, r)  # noqa: E731
    i, f, o = (jax.nn.sigmoid(gate(g)) for g in "ifo")
    c = f * c + i * jnp.tanh(gate("g"))
    h = o * jnp.tanh(c)
    return (c, h), _dense(p["out_proj"], h, r)


def _teacher_forced(params, model, feats, masks, tokens, forbid_special,
                    precision, read):
    """One plain walk over ``tokens`` [B, T] under teacher forcing (inputs
    are ``tokens`` shifted right behind BOS): at every position ``read(logp
    [B, V], position)`` picks what the caller wants of the distribution;
    whatever it returns is zeroed after a row's EOS. -> its outputs as
    ``[B, T]`` arrays."""
    r = rounder(precision)
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        memory, proj, mask, carry = encode(params, model, feats, masks, r)
        tokens = jnp.asarray(tokens, jnp.int32)

        def position(state, at):
            tok, t = at
            carry, prev, alive = state
            carry, logits = _step(params["params"]["cell"], carry, prev,
                                  memory, proj, mask, r)
            if forbid_special:
                logits = logits.at[:, PAD_ID].set(-1.0e9).at[:, BOS_ID].set(-1.0e9)
            out = jax.tree.map(lambda x: jnp.where(alive, x, jnp.zeros_like(x)),
                               read(jax.nn.log_softmax(logits, axis=-1), t))
            alive = alive & (tok != EOS_ID) & (tok != PAD_ID)
            return (carry, tok, alive), out

        B, T = tokens.shape
        start = (carry, jnp.full((B,), BOS_ID, jnp.int32), jnp.ones((B,), bool))
        _, out = jax.lax.scan(position, start, (tokens.T, jnp.arange(T)))
        return jax.tree.map(lambda x: x.T, out)


def _picked(logp, tok):
    return jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]


def token_logprobs(params, model: dict, feats, masks, tokens,
                   forbid_special: bool = False, precision: str = "float32"):
    """Per-position log-probability of ``tokens`` [B, T] under teacher
    forcing (inputs are ``tokens`` shifted right behind BOS); positions
    after a row's EOS read 0. One LSTM layer, as the configuration has."""
    tokens = jnp.asarray(tokens, jnp.int32)
    return _teacher_forced(params, model, feats, masks, tokens, forbid_special,
                           precision, lambda logp, t: _picked(logp, tokens[:, t]))


def beam_logprobs(params, model: dict, feats, masks, tokens, beam: int,
                  precision: str = "float32"):
    """What a beam search of width ``beam`` that emitted ``tokens`` [B, T]
    may be held to, position by position under teacher forcing along
    ``tokens`` (PAD and BOS forbidden, as the decode loops have it):
    ``(logp, edge)``, each [B, T] and 0 after a row's EOS. ``logp`` is the
    log-probability of the token, ``edge`` that of the ``beam``-th most
    probable token there. A beam keeps ``beam`` candidates a step out of
    ``beam * V``, so every token of a hypothesis it kept is among the
    ``beam`` most probable after its prefix: ``logp >= edge`` wherever the
    search and this reference agree on the arithmetic. The ``eval`` job's
    reference; only that job calls it."""
    tokens = jnp.asarray(tokens, jnp.int32)

    def read(logp, t):
        return _picked(logp, tokens[:, t]), jax.lax.top_k(logp, beam)[0][:, -1]

    return _teacher_forced(params, model, feats, masks, tokens, True,
                           precision, read)


def beam_search(params, model: dict, feats, masks, beam: int, max_len: int,
                length_penalty: float = 0.0, precision: str = "float32"):
    """The plain beam search of width ``beam`` that the ``eval`` job holds the
    program's to: -> (tokens [B, max_len], PAD after a caption's EOS; score
    [B], the caption's summed log-probability, over ``length ** penalty``
    where a penalty is given). Written from the algorithm: every clip keeps
    ``beam`` hypotheses; a step scores each hypothesis' every next token
    (PAD and BOS forbidden), a hypothesis that has ended goes on with PAD at
    no cost, and the ``beam`` best of the ``beam * V`` candidates are kept;
    the first step has one live hypothesis. The caption is the best-scoring
    hypothesis after ``max_len`` steps. Only the ``eval`` job calls it, in
    blocks of rows; at a lower ``precision`` it is that job's control."""
    r = rounder(precision)
    W = int(beam)
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        bank = encode(params, model, feats, masks, r)
        # a clip's hypotheses lie side by side: row b * W + w
        memory, proj, mask, c, h = (jnp.repeat(x, W, axis=0)
                                    for x in (*bank[:3], *bank[3]))
        B = memory.shape[0] // W
        clip = jnp.arange(B)[:, None] * W

        def step(state, t):
            (c, h), prev, score, done, tokens = state
            (c, h), logits = _step(params["params"]["cell"], (c, h), prev,
                                   memory, proj, mask, r)
            logits = logits.at[:, PAD_ID].set(-1.0e9).at[:, BOS_ID].set(-1.0e9)
            logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, W, -1)
            V = logp.shape[-1]
            ended = jnp.full((V,), -1.0e9).at[PAD_ID].set(0.0)
            logp = jnp.where(done[:, :, None], ended, logp)
            score, flat = jax.lax.top_k(
                (score[:, :, None] + logp).reshape(B, W * V), W)
            parent, tok = flat // V, (flat % V).astype(jnp.int32)
            rows = (clip + parent).reshape(-1)
            tokens = jnp.take_along_axis(tokens, parent[:, :, None], axis=1)
            tokens = tokens.at[:, :, t].set(tok)
            done = jnp.take_along_axis(done, parent, axis=1) | (tok == EOS_ID)
            return ((c[rows], h[rows]), tok.reshape(-1), score, done,
                    tokens), None

        start = ((c, h), jnp.full((B * W,), BOS_ID, jnp.int32),
                 jnp.full((B, W), -1.0e9).at[:, 0].set(0.0),
                 jnp.zeros((B, W), bool),
                 jnp.full((B, W, max_len), PAD_ID, jnp.int32))
        (_, _, score, _, tokens), _ = jax.lax.scan(step, start,
                                                   jnp.arange(max_len))
        if length_penalty > 0.0:
            length = jnp.maximum((tokens != PAD_ID).sum(-1), 1)
            score = score / length.astype(jnp.float32) ** length_penalty
        best = jnp.argmax(score, axis=1)
        return (jnp.take_along_axis(tokens, best[:, None, None], axis=1)[:, 0],
                jnp.take_along_axis(score, best[:, None], axis=1)[:, 0])
