"""Hybrid sparse-softmax / linear-attention caption decoder
(``ModelConfig.decoder = "sparse_linear"``): a pre-norm residual stack behind
a long video prefix, each layer mixing tokens one of two ways.

The third decoder kind, reached through the same :class:`~cst_captioning_tpu.
models.captioner.CaptionModel` methods as the other two. The sizes are fields
of ``ModelConfig`` under the key names of the published ``config.json`` they
are read from (MiniCPM-SALA; benchmark/configs/minicpm_sala_8l.json).

- **Prefix.** Each modality's frame features go through that modality's
  linear projection (``embed_<name>``, no bias) to ``hidden_size``, one slot a
  frame. **A missing slot is as if it were not there:** a clip's valid slots
  are moved to the front in their order (a stable sort), so a clip with ``n``
  valid slots is the sequence of those ``n`` followed by its caption; slot
  ``i`` of them sits at position ``i`` and caption token ``t`` at ``n + t``,
  BOS first. Nothing from position ``n`` of the prefix on is ever a key, a
  value or part of a state.
- **Stream.** ``h0 = scale_emb * E[token]`` for caption tokens; every branch
  joins as ``h += (scale_depth / sqrt(published_layers)) * branch(norm(h))``,
  the mixer first, then the gated FFN; logits ``head(norm(h)) / (hidden_size
  / dim_model_base)``, untied, float32.
- **``minicpm4`` layers** (ops/sparse_attention.py): grouped-query softmax
  attention without rope, RMSNorm on each head's q and k, over the key
  blocks the query selects, ``out = o_proj(attn * sigmoid(gate(x)))``.
- **``lightning-attn`` layers** (ops/linear_attention.py): rope and per-head
  RMSNorm on q and k, a decaying ``[head_dim, head_dim]`` state a head in
  float32, RMSNorm on the output, the same sigmoid gate.

**Two kinds of state in one beam.** The prefix's keys, values and compressed
keys of the sparse layers are per clip: they ride in ``EncoderOutput.memory``
/ ``memory_proj`` (``memory_mask`` says how many there are), which the lane
decode closes over, so a clip's beams read one copy. What a lane owns rides
in :class:`SparseLinearCarry`: its caption's keys and values (``max_len``
positions a sparse layer), the linear layers' states, how many tokens it
holds, and what its last call counted. Every leaf is batch-major, so the
decode loops gather it by parent beam like an LSTM carry.

The last layer's mixer output and FFN over the prefix feed nothing and are
not run: the prefix leaves that layer its keys, values or state only.
"""

from __future__ import annotations

import math

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.latent_moe import rms_norm, rope
from cst_captioning_tpu.ops import linear_attention as linear
from cst_captioning_tpu.ops import sparse_attention as sparse

SPARSE, LINEAR = "minicpm4", "lightning-attn"
# positions a chunk of the linear layers' prefix scan, and rows a block of
# the prefix's FFN (the [rows, intermediate_size] products of 32768 rows at
# once would be 3 GB of temporaries)
LINEAR_CHUNK, FFN_ROWS = 256, 8192


def mixer_impl() -> str:
    """How the prefix runs the two mixers: one kernel each on the TPU
    (ops/sparse_attention.py, ops/linear_attention.py), the same arithmetic
    as compiled loops elsewhere (the kernels' parity oracle, and what a
    gradient can pass through)."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


@flax.struct.dataclass
class SparseLinearCarry:
    """What one lane owns; every leaf batch-major."""

    k: tuple[jnp.ndarray, ...]      # a sparse layer: [B, max_len, G, d]
    v: tuple[jnp.ndarray, ...]
    state: tuple[jnp.ndarray, ...]  # a linear layer: [B, H, d, d] float32
    pos: jnp.ndarray                # [B] int32: caption tokens held so far
    # [B, sparse layers, 3] int32: what the LAST call counted for this row
    # in each sparse layer, a key/value group a query: keys seen, keys
    # attended to, queries under the dense length. The decode loops tally it
    # (obs counters sparse.*); nothing reads it back into the model
    counted: jnp.ndarray


def sparse_spec(cfg: ModelConfig) -> sparse.SparseSpec:
    return sparse.SparseSpec(
        kernel=cfg.sparse_kernel_size, stride=cfg.sparse_kernel_stride,
        block=cfg.sparse_block_size, topk=cfg.sparse_topk,
        window=cfg.sparse_window_size, init_blocks=cfg.sparse_init_blocks,
        dense_len=cfg.sparse_dense_len)


def rope_inv_freq(cfg: ModelConfig) -> jnp.ndarray:
    d = cfg.lightning_head_dim
    return float(cfg.rope_theta) ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)


def compact_prefix(cfg: ModelConfig, embed, feats, masks):
    """Each modality's features through its projection ``embed[name]`` -> (x
    [B, P, h] in ``cfg.dtype`` with each row's valid slots first and zeros
    behind them, n [B]: how many there are)."""
    dt = jnp.dtype(cfg.dtype)
    names = cfg.modality_names
    valid = jnp.concatenate([masks[n] > 0 for n in names], axis=1)
    x = jnp.concatenate(
        [feats[n].astype(dt) @ embed[n].astype(dt) for n in names], 1)
    order = jnp.argsort(jnp.logical_not(valid), axis=1, stable=True)
    x = jnp.take_along_axis(x, order[:, :, None], axis=1)
    n = valid.sum(axis=1).astype(jnp.int32)
    live = jnp.arange(x.shape[1])[None, :] < n[:, None]
    return x * live[..., None].astype(dt), n


def _heads(x, n: int):
    return x.reshape(x.shape[:-1] + (n, x.shape[-1] // n))


class SparseLinearLayer(nn.Module):
    """One block: a mixer of kind ``mixer``, then the gated FFN."""

    cfg: ModelConfig
    mixer: str
    index: int          # among the held layers

    def setup(self):
        c = self.cfg
        pd = jnp.dtype(c.param_dtype)
        h, m = c.hidden_size, c.intermediate_size
        w = nn.initializers.normal(c.initializer_range)
        one = nn.initializers.ones
        if self.mixer == SPARSE:
            H, G, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        else:
            H = G = c.lightning_nh
            d = c.lightning_head_dim
        shapes = {
            "input_layernorm": (one, (h,)),
            "q_proj": (w, (h, H * d)), "k_proj": (w, (h, G * d)),
            "v_proj": (w, (h, G * d)), "o_proj": (w, (H * d, h)),
            "o_gate": (w, (h, H * d)),
            "q_norm": (one, (d,)), "k_norm": (one, (d,)),
            "post_attention_layernorm": (one, (h,)),
            "gate_proj": (w, (h, m)), "up_proj": (w, (h, m)),
            "down_proj": (w, (m, h)),
        }
        if self.mixer == LINEAR:
            shapes["o_norm"] = (one, (H * d,))
        self.p = {name: self.param(name, init, shape, pd)
                  for name, (init, shape) in shapes.items()}
        self.sizes = (H, G, d)

    @property
    def branch_scale(self) -> float:
        return self.cfg.scale_depth / math.sqrt(self.cfg.published_layers)

    def slopes(self):
        c = self.cfg
        return linear.decay_slopes(
            c.lightning_nh, c.first_layer_index + self.index, c.published_layers)

    def qkv(self, x, positions):
        """x [..., h] normed -> q [..., H, d], k, v [..., G, d]; per-head
        RMSNorm on q and k, rope on the linear layers' at ``positions``."""
        p, dt = self.p, x.dtype
        H, G, _ = self.sizes
        eps = self.cfg.rms_norm_eps
        q = rms_norm(_heads(x @ p["q_proj"].astype(dt), H), p["q_norm"], eps)
        k = rms_norm(_heads(x @ p["k_proj"].astype(dt), G), p["k_norm"], eps)
        v = _heads(x @ p["v_proj"].astype(dt), G)
        if self.mixer == LINEAR:
            inv_freq = rope_inv_freq(self.cfg)
            q, k = rope(q, positions, inv_freq), rope(k, positions, inv_freq)
        return q, k, v

    def mixed(self, x, attn):
        """The mixer's branch from its heads' outputs ``attn [..., H, d]``
        and the normed input ``x``: output norm (linear), gate, projection."""
        p, dt = self.p, x.dtype
        attn = attn.reshape(attn.shape[:-2] + (-1,))
        if self.mixer == LINEAR:
            attn = rms_norm(attn, p["o_norm"], self.cfg.rms_norm_eps)
        gate = jax.nn.sigmoid(x @ p["o_gate"].astype(dt))
        return (attn * gate) @ p["o_proj"].astype(dt)

    def ffn(self, x):
        """x [N, h] -> the FFN branch, in blocks of ``FFN_ROWS``."""
        c, p, dt = self.cfg, self.p, x.dtype

        def rows(y):
            y = rms_norm(y, p["post_attention_layernorm"], c.rms_norm_eps)
            up = jax.nn.silu(y @ p["gate_proj"].astype(dt)) * (y @ p["up_proj"].astype(dt))
            return up @ p["down_proj"].astype(dt)

        N, blk = x.shape[0], FFN_ROWS
        if N <= blk or N % blk:
            return rows(x)
        return jax.lax.map(rows, x.reshape(N // blk, blk, -1)).reshape(x.shape)


class SparseLinearDecoder(nn.Module):
    """Prefix projector, the stack, final norm and head."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        kinds = c.mixer_types
        if len(kinds) != c.num_hidden_layers or not kinds or any(
                k not in (SPARSE, LINEAR) for k in kinds):
            raise ValueError(
                f"mixer_types {kinds} must name num_hidden_layers "
                f"{c.num_hidden_layers} mixers, each {SPARSE!r} or {LINEAR!r}")
        if c.num_attention_heads % max(c.num_key_value_heads, 1) \
                or c.lightning_head_dim % 2 or c.published_layers < 1 \
                or c.sparse_block_size % c.sparse_kernel_stride:
            raise ValueError(
                "num_key_value_heads must divide num_attention_heads, "
                "lightning_head_dim be even, published_layers >= 1 and "
                "sparse_kernel_stride divide sparse_block_size")
        pd = jnp.dtype(c.param_dtype)
        w = nn.initializers.normal(c.initializer_range)
        self.embed = {name: self.param(f"embed_{name}", w, (dim, c.hidden_size), pd)
                      for name, dim in c.modalities}
        self.embed_tokens = self.param(
            "embed_tokens", w, (c.vocab_size, c.hidden_size), pd)
        self.layers = [SparseLinearLayer(c, kind, i, name=f"layers_{i}")
                       for i, kind in enumerate(kinds)]
        self.norm = self.param("norm", nn.initializers.ones, (c.hidden_size,), pd)
        self.lm_head = self.param("lm_head", w, (c.hidden_size, c.vocab_size), pd)

    def _logits(self, x):
        c = self.cfg
        x = rms_norm(x, self.norm, c.rms_norm_eps)
        return jnp.dot(x, self.lm_head.astype(x.dtype),
                       preferred_element_type=jnp.float32) \
            / (c.hidden_size / c.dim_model_base)

    def prefill(self, feats, masks):
        """-> (bank, n [B], carry): the prefix through the stack. ``bank`` is
        ``(keys, values, compressed keys)``, each a tuple over the sparse
        layers of per-clip arrays; ``carry`` a lane's empty caption cache and
        the linear layers' states after the prefix."""
        c = self.cfg
        spec = sparse_spec(c)
        x, n = compact_prefix(c, self.embed, feats, masks)
        B, P, h = x.shape
        positions = jnp.broadcast_to(jnp.arange(P), (B, P))
        keys, values, pooled, states, counted = [], [], [], [], []
        for i, layer in enumerate(self.layers):
            last = i + 1 == len(self.layers)
            p = layer.p
            y = rms_norm(x, p["input_layernorm"], c.rms_norm_eps)
            q, k, v = layer.qkv(y, positions)
            if layer.mixer == SPARSE:
                ck = sparse.compress_keys(k, spec)
                keys.append(k)
                values.append(v)
                pooled.append(ck)
                if last:
                    counted.append(jnp.zeros((B, 3), jnp.int32))
                    break
                attn, tally = sparse.sparse_prefill(
                    q, k, v, ck, n, spec, impl=mixer_impl())
                counted.append(tally)
            else:
                attn, state = linear.chunked_linear_attention(
                    q, k, v, layer.slopes(), n, LINEAR_CHUNK, mixer_impl())
                states.append(state)
                if last:
                    break
            x = x + layer.branch_scale * layer.mixed(y, attn)
            x = x + layer.branch_scale * layer.ffn(
                x.reshape(B * P, h)).reshape(B, P, h)
        dt = jnp.dtype(c.dtype)
        own = lambda kv: tuple(  # noqa: E731
            jnp.zeros((B, c.max_len) + a.shape[2:], dt) for a in kv)
        carry = SparseLinearCarry(
            own(keys), own(values), tuple(states), jnp.zeros((B,), jnp.int32),
            jnp.stack(counted, axis=1) if counted
            else jnp.zeros((B, 0, 3), jnp.int32))
        return (tuple(keys), tuple(values), tuple(pooled)), n, carry

    def step(self, carry: SparseLinearCarry, token, bank, n):
        """One token a row -> (carry, logits [N, V] float32)."""
        c = self.cfg
        spec = sparse_spec(c)
        dt = jnp.dtype(c.dtype)
        keys, values, pooled = bank
        x = self.embed_tokens.astype(dt)[token] * jnp.asarray(c.scale_emb, dt)
        t = carry.pos
        own_k, own_v, states, counted = [], [], [], []
        si = li = 0
        for layer in self.layers:
            y = rms_norm(x, layer.p["input_layernorm"], c.rms_norm_eps)
            q, k, v = layer.qkv(y, n + t)
            if layer.mixer == SPARSE:
                attn, k_own, v_own, tally = sparse.sparse_step(
                    q, k, v, keys[si], values[si], pooled[si], n, t,
                    carry.k[si], carry.v[si], spec)
                own_k.append(k_own)
                own_v.append(v_own)
                counted.append(tally)
                si += 1
            else:
                attn, state = linear.linear_attention_step(
                    carry.state[li], q, k, v, layer.slopes())
                states.append(state)
                li += 1
            x = x + layer.branch_scale * layer.mixed(y, attn)
            x = x + layer.branch_scale * layer.ffn(x)
        carry = SparseLinearCarry(
            tuple(own_k), tuple(own_v), tuple(states), t + 1,
            jnp.stack(counted, axis=1) if counted else carry.counted)
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
