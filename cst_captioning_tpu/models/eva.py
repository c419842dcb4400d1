"""EVA-attention byte-level caption decoder (``ModelConfig.decoder = "eva"``):
a pre-norm residual stack behind a long video prefix, every layer mixing
tokens by EVA attention (ops/eva_attention.py): exact keys inside a window,
one learned summary a chunk of the windows before it, one softmax over both.

The fourth decoder kind, reached through the same :class:`~cst_captioning_tpu.
models.captioner.CaptionModel` methods as the other three. The sizes are
fields of ``ModelConfig`` under the key names of the published ``config.json``
they are read from (EvaByte; benchmark/configs/evabyte_8l.json).

- **Prefix.** As the sparse/linear decoder's (models/sparse_linear.py): each
  modality's features through its linear projection (``embed_<name>``, no
  bias), a clip's valid slots moved to the front in their order, slot ``i`` of
  the ``n`` valid ones at position ``i`` and caption token ``t`` at ``n + t``,
  BOS first; nothing from position ``n`` of the prefix on exists.
- **Stream.** Float32 (``fp32_skip_add``): ``h0 = E[token]``; ``h += branch(
  norm(h))`` for the mixer, then the gated FFN; ``norm(x; g) = x / rms(x) *
  (1 + g)`` (``norm_add_unit_offset``, ``g`` starts at 0). The branches
  compute in ``cfg.dtype``. Logits ``norm(h) @ lm_head[:, :vocab_size]`` in
  float32: the head holds ``num_pred_heads`` blocks of ``vocab_size`` columns
  (block ``j`` predicts byte ``t + 1 + j``) and plain decoding reads block 0.
- **Layer.** 32 heads with rope on q and k; a head's summary of a chunk is
  the softmax pooling of the chunk's rotated keys and its values under the
  head's learned ``phi``, the pooled key moved by its ``mu``.

**A state that changes kind.** What a clip's lanes share rides in
``EncoderOutput``: every whole prefix chunk's summary (``memory``) and the
exact keys of the window the prefix ends in (``memory_proj``), held once a
clip. What a lane owns rides in :class:`EvaCarry`: its caption's keys and
values, and the summaries it has made of the chunks its caption completed;
while the caption stays in the prefix's last window the lane reads those
chunks' exact keys, once it has crossed into the next it reads their
summaries. Every leaf is batch-major, so the decode loops gather it by parent
beam like an LSTM carry.

The last layer's mixer output and FFN over the prefix feed nothing and are
not run: the prefix leaves that layer its keys, values and summaries only.
"""

from __future__ import annotations

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.latent_moe import rms_norm, rope
from cst_captioning_tpu.models.sparse_linear import (
    FFN_ROWS,
    compact_prefix,
    mixer_impl,
)
from cst_captioning_tpu.ops import eva_attention as eva


@flax.struct.dataclass
class EvaCarry:
    """What one lane owns; every leaf batch-major, a tuple over the layers."""

    # [B, H, slots x chunk, d]: the caption's keys in their frame of whole
    # chunks (``ops.eva_attention.own_frame``)
    k: tuple[jnp.ndarray, ...]
    v: tuple[jnp.ndarray, ...]
    ks: tuple[jnp.ndarray, ...]     # [B, H, slots, d]: its own summaries
    vs: tuple[jnp.ndarray, ...]
    pos: jnp.ndarray                # [B] int32: caption tokens held so far
    # [B, 1, 3] int32: what the LAST call counted for this row, a query:
    # exact keys attended, summaries attended, window crossings. The decode
    # loops tally it (obs counters eva.*); nothing reads it back
    counted: jnp.ndarray


def eva_spec(cfg: ModelConfig) -> eva.EvaSpec:
    return eva.EvaSpec(window=cfg.window_size, chunk=cfg.chunk_size)


def head_dim(cfg: ModelConfig) -> int:
    return cfg.hidden_size // cfg.num_attention_heads


def rope_inv_freq(cfg: ModelConfig) -> jnp.ndarray:
    d = head_dim(cfg)
    return float(cfg.rope_theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def unit_norm(x, g, eps: float, dtype):
    """``x / rms(x) * (1 + g)`` in float32, handed on in ``dtype``."""
    return rms_norm(x.astype(jnp.float32), 1.0 + g.astype(jnp.float32),
                    eps).astype(dtype)


class EvaLayer(nn.Module):
    """One block: EVA attention, then the gated FFN."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        pd = jnp.dtype(c.param_dtype)
        h, m = c.hidden_size, c.intermediate_size
        H, d = c.num_attention_heads, head_dim(c)
        w = nn.initializers.normal(c.init_std)
        zero = nn.initializers.zeros
        shapes = {
            "input_layernorm": (zero, (h,)),
            "q_proj": (w, (h, h)), "k_proj": (w, (h, h)),
            "v_proj": (w, (h, h)), "o_proj": (w, (h, h)),
            "phi": (w, (H, d)), "mu": (w, (H, d)),
            "post_attention_layernorm": (zero, (h,)),
            "gate_proj": (w, (h, m)), "up_proj": (w, (h, m)),
            "down_proj": (w, (m, h)),
        }
        self.p = {name: self.param(name, init, shape, pd)
                  for name, (init, shape) in shapes.items()}

    def qkv(self, x, positions):
        """The stream ``x [..., h]`` -> q, k, v [..., H, d], q and k rotated
        at ``positions``."""
        c, p = self.cfg, self.p
        dt = jnp.dtype(c.dtype)
        y = unit_norm(x, p["input_layernorm"], c.rms_norm_eps, dt)
        heads = lambda a: a.reshape(  # noqa: E731
            a.shape[:-1] + (c.num_attention_heads, head_dim(c)))
        inv_freq = rope_inv_freq(c)
        q = rope(heads(y @ p["q_proj"].astype(dt)), positions, inv_freq)
        k = rope(heads(y @ p["k_proj"].astype(dt)), positions, inv_freq)
        return q, k, heads(y @ p["v_proj"].astype(dt))

    def mixed(self, attn):
        """The mixer's branch from its heads' outputs ``attn [..., H, d]``."""
        attn = attn.reshape(attn.shape[:-2] + (-1,))
        return (attn @ self.p["o_proj"].astype(attn.dtype)).astype(jnp.float32)

    def ffn(self, x):
        """The stream ``x [N, h]`` -> the FFN branch, in blocks of rows."""
        c, p = self.cfg, self.p
        dt = jnp.dtype(c.dtype)

        def rows(x):
            y = unit_norm(x, p["post_attention_layernorm"], c.rms_norm_eps, dt)
            up = jax.nn.silu(y @ p["gate_proj"].astype(dt)) * (y @ p["up_proj"].astype(dt))
            return (up @ p["down_proj"].astype(dt)).astype(jnp.float32)

        N, blk = x.shape[0], FFN_ROWS
        if N <= blk or N % blk:
            return rows(x)
        return jax.lax.map(rows, x.reshape(N // blk, blk, -1)).reshape(x.shape)


class EvaDecoder(nn.Module):
    """Prefix projector, the stack, final norm and the eight-block head."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        if c.num_hidden_layers < 1 or c.hidden_size % max(c.num_attention_heads, 1) \
                or head_dim(c) % 2 or c.chunk_size < 1 \
                or c.window_size % c.chunk_size or c.num_pred_heads < 1:
            raise ValueError(
                "decoder='eva' needs num_hidden_layers >= 1, num_attention_heads "
                "dividing hidden_size into even heads, chunk_size dividing "
                "window_size and num_pred_heads >= 1")
        pd = jnp.dtype(c.param_dtype)
        w = nn.initializers.normal(c.init_std)
        self.embed = {name: self.param(f"embed_{name}", w, (dim, c.hidden_size), pd)
                      for name, dim in c.modalities}
        self.embed_tokens = self.param(
            "embed_tokens", w, (c.vocab_size, c.hidden_size), pd)
        self.layers = [EvaLayer(c, name=f"layers_{i}")
                       for i in range(c.num_hidden_layers)]
        self.norm = self.param("norm", nn.initializers.zeros, (c.hidden_size,), pd)
        self.lm_head = self.param(
            "lm_head", w, (c.hidden_size, c.num_pred_heads * c.vocab_size), pd)

    def _logits(self, x):
        c = self.cfg
        y = unit_norm(x, self.norm, c.rms_norm_eps, jnp.dtype(c.dtype))
        return jnp.dot(y, self.lm_head[:, :c.vocab_size].astype(y.dtype),
                       preferred_element_type=jnp.float32)

    def prefill(self, feats, masks):
        """-> (bank, n [B], carry): the prefix through the stack. ``bank`` is
        ``((summary keys, summary values), (window keys, window values,
        start))``: tuples over the layers of per-clip head-major arrays
        (``ops.eva_attention.window_slice``), ``start`` [B] the position each
        clip's window slice begins at; ``carry`` a lane's caption cache
        before its first token and what the prefix's queries counted."""
        c = self.cfg
        spec = eva_spec(c)
        x, n = compact_prefix(c, self.embed, feats, masks)
        x = x.astype(jnp.float32)
        B, P, h = x.shape
        positions = jnp.broadcast_to(jnp.arange(P), (B, P))
        start = eva.window_start(n, P, spec)
        # a layer's (keys, values) of each kind it leaves the caption
        sums, near, own = [], [], []
        for i, layer in enumerate(self.layers):
            q, k, v = layer.qkv(x, positions)
            ks, vs = eva.chunk_summaries(k, v, layer.p["phi"], layer.p["mu"], spec)
            sums.append((ks.transpose(0, 2, 1, 3), vs.transpose(0, 2, 1, 3)))
            near.append([eva.window_slice(a, start, spec) for a in (k, v)])
            own.append([eva.own_frame(a, n, c.max_len, spec) for a in (k, v)])
            if i + 1 == len(self.layers):
                break
            attn = eva.eva_prefill(q, k, v, ks, vs, n, spec, impl=mixer_impl())
            x = x + layer.mixed(attn)
            x = x + layer.ffn(x.reshape(B * P, h)).reshape(B, P, h)
        none = tuple(
            jnp.zeros((B, c.num_attention_heads, eva.own_slots(c.max_len, spec),
                       head_dim(c)), jnp.dtype(c.dtype)) for _ in self.layers)
        live = positions < n[:, None]
        exact, summary = eva.key_counts(positions, spec)
        counted = jnp.stack([jnp.where(live, exact, 0).sum(-1),
                             jnp.where(live, summary, 0).sum(-1),
                             jnp.zeros_like(n)], axis=-1).astype(jnp.int32)
        carry = EvaCarry(*zip(*own), none, none, jnp.zeros((B,), jnp.int32),
                         counted[:, None])
        return (tuple(zip(*sums)), (*zip(*near), start)), n, carry

    def step(self, carry: EvaCarry, token, bank, n):
        """One token a row -> (carry, logits [N, V] float32)."""
        c = self.cfg
        spec = eva_spec(c)
        (sums_k, sums_v), (near_k, near_v, start) = bank
        x = self.embed_tokens.astype(jnp.dtype(c.dtype))[token].astype(jnp.float32)
        t = carry.pos
        states = []
        for i, layer in enumerate(self.layers):
            q, k, v = layer.qkv(x, n + t)
            attn, *state, tally = eva.eva_step(
                q, k, v, sums_k[i], sums_v[i], near_k[i], near_v[i], start, n,
                t, carry.k[i], carry.v[i], carry.ks[i], carry.vs[i],
                layer.p["phi"], layer.p["mu"], spec)
            states.append(state)
            x = x + layer.mixed(attn)
            x = x + layer.ffn(x)
        carry = EvaCarry(*zip(*states), t + 1, tally[:, None])
        return carry, self._logits(x)

    def __call__(self, feats, masks, labels):
        """Teacher forcing: the prefix, then a step a caption position ->
        logits [B, T, V] float32; ``logits[:, t]`` predicts ``labels[:, t]``."""
        c = self.cfg
        B, T = labels.shape
        if self.is_initializing():
            # the parameters are all that is wanted: every layer declares
            # its own and no forward runs (eager, at the published widths)
            for layer in self.layers:
                layer.p
            # logits are float32 on every path
            return jnp.zeros((B, T, c.vocab_size), jnp.float32)  # graftlint: disable=GL005
        bank, n, carry = self.prefill(feats, masks)
        inputs = jnp.concatenate(
            [jnp.full((B, 1), BOS_ID, labels.dtype), labels[:, :-1]], axis=1)
        logits = []
        for token in inputs.T:
            carry, out = self.step(carry, token, bank, n)
            logits.append(out)
        return jnp.stack(logits, axis=1)
