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
    tokens = jnp.asarray(tokens, jnp.int32)
    return _walk(params, model, feats, masks, tokens, forbid_special, precision,
                 lambda logp, t: (jnp.take_along_axis(
                     logp, tokens[:, t, None], 1)[:, 0],))[0]


def beam_logprobs(params, model, feats, masks, tokens, beam,
                  precision="float32"):
    """For the ``eval`` job: along ``tokens`` (PAD and BOS forbidden), each
    token's log-probability and that of the ``beam``-th most probable token
    there; each [B, T], zero after a row's EOS."""
    tokens = jnp.asarray(tokens, jnp.int32)

    def read(logp, t):
        return (jnp.take_along_axis(logp, tokens[:, t, None], 1)[:, 0],
                jax.lax.top_k(logp, beam)[0][:, -1])

    return _walk(params, model, feats, masks, tokens, True, precision, read)


def _banks(params, model, feats, masks, precision):
    """-> (the cell's parameters, memory [B, M, E], its keys, (c, h))."""
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
    return cell, memory, keys, (c, h)


def _logp(cell, memory, keys, c, h, prev, forbid_special, precision):
    """One position: -> (c, h, log-probabilities [B, V] of the next token)."""
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
    return c, h, jax.nn.log_softmax(logits, -1)


def _walk(params, model, feats, masks, tokens, forbid_special, precision, read):
    """Position by position under teacher forcing; ``read(logp [B, V], t)``
    gives a tuple of [B] readings, zeroed after a row's EOS."""
    with jax.default_matmul_precision("highest"):
        cell, memory, keys, (c, h) = _banks(params, model, feats, masks,
                                            precision)
        tokens = jnp.asarray(tokens, jnp.int32)
        prev = jnp.full(tokens.shape[:1], BOS, jnp.int32)
        alive = jnp.ones(tokens.shape[:1], bool)
        columns = []
        for t in range(tokens.shape[1]):
            c, h, logp = _logp(cell, memory, keys, c, h, prev, forbid_special,
                               precision)
            columns.append([jnp.where(alive, x, jnp.zeros_like(x))
                            for x in read(logp, t)])
            alive &= (tokens[:, t] != EOS) & (tokens[:, t] != PAD)
            prev = tokens[:, t]
        return tuple(jnp.stack(c, 1) for c in zip(*columns))


def beam_search(params, model, feats, masks, beam, max_len, length_penalty=0.0,
                precision="float32"):
    """For the ``eval`` job: a plain beam search of width ``beam`` -> (tokens
    [B, max_len], PAD after EOS; score [B]). A clip's hypotheses are kept as
    [B, beam]; one that has ended is carried on with PAD at no cost."""
    with jax.default_matmul_precision("highest"):
        cell, memory, keys, (c, h) = _banks(params, model, feats, masks,
                                            precision)
        B, W = memory.shape[0], int(beam)
        wide = lambda x: jnp.repeat(x[:, None], W, 1)           # noqa: E731
        flat = lambda x: x.reshape((B * W,) + x.shape[2:])      # noqa: E731
        memory, keys, c, h = (flat(wide(x)) for x in (memory, keys, c, h))
        prev = jnp.full((B, W), BOS, jnp.int32)
        score = jnp.where(jnp.arange(W) == 0, 0.0, -1e9) * jnp.ones((B, 1))
        done = jnp.zeros((B, W), bool)
        tokens = jnp.full((B, W, max_len), PAD, jnp.int32)
        for t in range(max_len):
            c, h, logp = _logp(cell, memory, keys, c, h, flat(prev), True,
                               precision)
            V = logp.shape[-1]
            over = jnp.where(jnp.arange(V) == PAD, 0.0, -1e9)
            total = score[..., None] + jnp.where(done[..., None], over,
                                                 logp.reshape(B, W, V))
            score, best = jax.lax.top_k(total.reshape(B, W * V), W)
            parent, prev = best // V, (best % V).astype(jnp.int32)
            pick = lambda x: jnp.take_along_axis(                # noqa: E731
                x.reshape((B, W) + x.shape[1:]),
                parent.reshape((B, W) + (1,) * (x.ndim - 1)), 1)
            c, h = flat(pick(c)), flat(pick(h))
            tokens = jnp.take_along_axis(tokens, parent[..., None], 1)
            tokens = tokens.at[:, :, t].set(prev)
            done = jnp.take_along_axis(done, parent, 1) | (prev == EOS)
        if length_penalty > 0:
            score = score / jnp.maximum((tokens != PAD).sum(-1), 1) ** length_penalty
        top = jnp.argmax(score, 1)
        return tokens[jnp.arange(B), top], score[jnp.arange(B), top]
