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

The one entry every job calls, and every configuration's reference module
has, is :func:`token_logprobs`; it is differentiable in ``params``.
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


def token_logprobs(params, model: dict, feats, masks, tokens,
                   forbid_special: bool = False, precision: str = "float32"):
    """Per-position log-probability of ``tokens`` [B, T] under teacher
    forcing (inputs are ``tokens`` shifted right behind BOS); positions
    after a row's EOS read 0. One LSTM layer, as the configuration has."""
    r = rounder(precision)
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        memory, proj, mask, carry = encode(params, model, feats, masks, r)
        tokens = jnp.asarray(tokens, jnp.int32)

        def position(state, tok):
            carry, prev, alive = state
            carry, logits = _step(params["params"]["cell"], carry, prev,
                                  memory, proj, mask, r)
            if forbid_special:
                logits = logits.at[:, PAD_ID].set(-1.0e9).at[:, BOS_ID].set(-1.0e9)
            lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     tok[:, None], axis=-1)[:, 0]
            out = jnp.where(alive, lp, 0.0)
            alive = alive & (tok != EOS_ID) & (tok != PAD_ID)
            return (carry, tok, alive), out

        B = tokens.shape[0]
        start = (carry, jnp.full((B,), BOS_ID, jnp.int32), jnp.ones((B,), bool))
        _, out = jax.lax.scan(position, start, tokens.T)
        return out.T
