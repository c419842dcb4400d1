"""Plain reference of the rehearsal's second architecture, the pooled
captioner: every modality's frames are averaged into ONE memory slot, an
additive attention weighs the slots, and an input-feed LSTM decodes. It exists
only here, to show that an architecture is files: this module (named by
``config.json``'s ``reference``), ``costs.py`` and the tolerances in
``config.json``; no shared file of the benchmark names it. Written on its
own, position by position, from the same equations the program implements
for ``encoder="meanpool"``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

PAD, BOS, EOS = 0, 1, 2


def _round(x, precision):
    if precision == "float32":
        return x
    return x.astype(jnp.dtype(precision)).astype(jnp.float32)


def _affine(p, x, precision):
    y = _round(x, precision) @ _round(p["kernel"], precision)
    return y + p.get("bias", 0.0)


def token_logprobs(params, model, feats, masks, tokens, forbid_special=False,
                   precision="float32"):
    """[B, T] log-probabilities of ``tokens`` under teacher forcing; zero
    after a row's EOS."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda x: jnp.asarray(x, jnp.float32), params)["params"]
        slots = []
        for name, _width in model["modalities"]:
            x = jnp.asarray(feats[name], jnp.float32)
            m = jnp.asarray(masks[name], jnp.float32)[..., None]
            pooled = (x * m).sum(1) / jnp.maximum(m.sum(1), 1.0)
            slots.append(jnp.tanh(_affine(p["encoder"]["embed_" + name], pooled,
                                          precision)))
        memory = jnp.stack(slots, axis=1)                        # [B, M, E]
        cell = p["cell"]
        keys = _round(memory, precision) @ _round(
            cell["attention"]["mem_proj"]["kernel"], precision)
        mean = memory.mean(1)
        c = jnp.tanh(_affine(p["init_c0"], mean, precision))
        h = jnp.tanh(_affine(p["init_h0"], mean, precision))
        tokens = jnp.asarray(tokens, jnp.int32)
        prev = jnp.full(tokens.shape[:1], BOS, jnp.int32)
        alive = jnp.ones(tokens.shape[:1], bool)
        columns = []
        for t in range(tokens.shape[1]):
            query = _affine(cell["attention"]["query_proj"], h, precision)
            energy = _affine(cell["attention"]["score"],
                             jnp.tanh(keys + query[:, None]), precision)[..., 0]
            context = (jax.nn.softmax(energy, -1)[..., None] * memory).sum(1)
            x = jnp.concatenate([cell["word_embed"]["embedding"][prev], context], -1)
            pre = {g: _affine(cell["lstm0"]["i" + g], x, precision)
                   + _affine(cell["lstm0"]["h" + g], h, precision) for g in "ifgo"}
            c = jax.nn.sigmoid(pre["f"]) * c + jax.nn.sigmoid(pre["i"]) * jnp.tanh(pre["g"])
            h = jax.nn.sigmoid(pre["o"]) * jnp.tanh(c)
            logits = _affine(cell["out_proj"], h, precision)
            if forbid_special:
                logits = logits.at[:, PAD].set(-1e9).at[:, BOS].set(-1e9)
            picked = jnp.take_along_axis(jax.nn.log_softmax(logits, -1),
                                         tokens[:, t, None], 1)[:, 0]
            columns.append(jnp.where(alive, picked, 0.0))
            alive &= (tokens[:, t] != EOS) & (tokens[:, t] != PAD)
            prev = tokens[:, t]
        return jnp.stack(columns, 1)
