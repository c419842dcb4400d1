"""Plain reference of the captioner both configurations run (Phan et al.,
arXiv:1712.09532, as `cst_captioning` builds it): frame embedding, additive
temporal attention (one slot per modality when the encoder mean-pools), an
input-feed LSTM decoder, a vocabulary projection. Straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision: no scan, no kernels,
no cache, no batching tricks, written from the equations and independent of
the program's modules. It reads the program's parameter tree (flax names) and
nothing else. Departures from the paper: the LSTM carry is initialised from
the masked mean of the memory through two dense layers (the program's
choice), and PAD/BOS are forbidden at decode time (``forbid_special=True``
reproduces the decode loops' distribution; teacher forcing leaves them in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PAD_ID, BOS_ID, EOS_ID = 0, 1, 2


def _dense(p, x):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def _f32(tree):
    return jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), tree)


def encode(params, encoder: str, modalities, feats, masks):
    """-> (memory [B, M, E], memory_proj [B, M, A], mask [B, M], (c0, h0))."""
    p = params["params"]
    banks, bmasks = [], []
    for name in modalities:
        x = jnp.asarray(feats[name], jnp.float32)
        m = jnp.asarray(masks[name], jnp.float32)
        if encoder == "meanpool":
            x = (x * m[..., None]).sum(1) / jnp.maximum(m.sum(1), 1.0)[:, None]
            banks.append(jnp.tanh(_dense(p["encoder"][f"embed_{name}"], x))[:, None])
            bmasks.append(jnp.ones((x.shape[0], 1), jnp.float32))
        else:
            banks.append(jnp.tanh(_dense(p["encoder"][f"embed_{name}"], x)))
            bmasks.append(m)
    memory = jnp.concatenate(banks, axis=1)
    mask = jnp.concatenate(bmasks, axis=1)
    memory = memory * mask[..., None]
    proj = memory @ p["cell"]["attention"]["mem_proj"]["kernel"]
    ctx0 = (memory * mask[..., None]).sum(1) / jnp.maximum(mask.sum(1), 1.0)[:, None]
    c0 = jnp.tanh(_dense(p["init_c0"], ctx0))
    h0 = jnp.tanh(_dense(p["init_h0"], ctx0))
    return memory, proj, mask, (c0, h0)


def _step(p, carry, token, memory, proj, mask):
    c, h = carry
    att = p["attention"]
    q = _dense(att["query_proj"], h)
    scores = (jnp.tanh(proj + q[:, None, :]) @ att["score"]["kernel"])[..., 0]
    scores = jnp.where(mask > 0, scores, -1.0e9)
    ctx = jnp.einsum("bm,bme->be", jax.nn.softmax(scores, axis=-1), memory)
    x = jnp.concatenate([p["word_embed"]["embedding"][token], ctx], axis=-1)
    lstm = p["lstm0"]
    gate = lambda g: _dense(lstm["i" + g], x) + _dense(lstm["h" + g], h)  # noqa: E731
    i, f, o = (jax.nn.sigmoid(gate(g)) for g in "ifo")
    c = f * c + i * jnp.tanh(gate("g"))
    h = o * jnp.tanh(c)
    return (c, h), _dense(p["out_proj"], h)


def token_logprobs(params, encoder: str, modalities, feats, masks, tokens,
                   forbid_special: bool = False):
    """Per-position log-probability of ``tokens`` [B, T] under teacher
    forcing (inputs are ``tokens`` shifted right behind BOS); positions
    after a row's EOS read 0. One LSTM layer, as both configurations have."""
    with jax.default_matmul_precision("highest"):
        params = _f32(params)
        memory, proj, mask, carry = encode(params, encoder, modalities,
                                           feats, masks)
        tokens = jnp.asarray(tokens, jnp.int32)
        B, T = tokens.shape
        prev = jnp.full((B,), BOS_ID, jnp.int32)
        alive = jnp.ones((B,), bool)
        out = []
        for t in range(T):
            carry, logits = _step(params["params"]["cell"], carry, prev,
                                  memory, proj, mask)
            if forbid_special:
                logits = logits.at[:, PAD_ID].set(-1.0e9).at[:, BOS_ID].set(-1.0e9)
            lp = jnp.take_along_axis(jax.nn.log_softmax(logits, axis=-1),
                                     tokens[:, t, None], axis=-1)[:, 0]
            out.append(jnp.where(alive, lp, 0.0))
            alive = alive & (tokens[:, t] != EOS_ID) & (tokens[:, t] != PAD_ID)
            prev = tokens[:, t]
        return jnp.stack(out, axis=1)
