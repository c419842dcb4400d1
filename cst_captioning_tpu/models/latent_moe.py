"""Latent-attention, routed-expert caption decoder (``ModelConfig.decoder =
"latent_moe"``): a pre-norm residual stack behind a video prefix.

The second decoder kind beside the attention-LSTM cell (models/decoder.py),
reached through the same :class:`~cst_captioning_tpu.models.captioner.
CaptionModel` methods, so beam search, the ``Evaluator``, ``cli/eval.py``,
presets and checkpoints run it as they run the LSTM. The sizes are fields of
``ModelConfig`` under the key names of the published ``config.json`` they are
read from (Kimi-K2 / DeepSeek-V3 family; benchmark/configs/kimi_k2_ep32.json).

- **Prefix.** Each modality's frame features go through that modality's
  linear projection (``embed_<name>``, no bias) to ``hidden_size``: one
  prefix slot a frame, slot index = position; a missing frame's slot is zero
  and masked out of every attention. Caption token ``t`` sits at position
  ``n_prefix + t``, BOS first.
- **Block.** ``x += MLA(norm(x)); x += FFN(norm(x))``, RMSNorm with float32
  statistics; after the last block ``norm`` and the untied head, logits in
  float32.
- **Latent attention (MLA).** Queries through a low-rank pair
  (``q_a_proj`` -> RMSNorm -> ``q_b_proj``); keys and values from one
  compressed vector a position, ``[c_kv | k_r]`` (``kv_lora_rank`` normed
  numbers and ``qk_rope_head_dim`` rotated ones, shared by all heads). **The
  cache holds that vector and nothing else.** Prefill and teacher forcing
  expand it through ``kv_b_proj`` (:meth:`LatentMoELayer.full`); a decode
  step uses the absorbed form (:meth:`LatentMoELayer.step`): ``q_nope``
  through ``kv_b_proj``'s key half scores against ``c_kv`` directly, and the
  weighted ``c_kv`` goes through its value half, so a step never re-expands
  the cache (tests/test_latent_moe.py holds the two forms together).
- **FFN.** The first ``first_k_dense_replace`` layers: one gated FFN of
  ``intermediate_size``. The others: ``shared(x) + sum_{e in chosen} w_e
  expert_e(x)`` with a float32 sigmoid router over all ``n_routed_experts``;
  the chosen are the ``num_experts_per_tok`` largest of ``score + bias``
  (the bias steers the choice only), ``w = score / sum(chosen scores) *
  routed_scaling_factor``.
- **The chip's share.** The layer holds ``experts_held`` consecutive experts
  (``expert_share_index`` says which), routes over all of them, normalises
  over all chosen, and computes the chosen experts it holds for every token
  routed to them: tokens are sorted by held expert and each expert walks its
  own rows in blocks, as many as it has (models/experts.py), so there is no
  capacity and no dropped token. What the absent experts would add is left
  out and the partial result goes on; nothing stands in for the other chips.

Every leaf of the decode state (:class:`LatentCarry`) is batch-major, the
position too, so the decode loops gather it by parent beam like an LSTM
carry.
"""

from __future__ import annotations

import math

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.experts import (  # noqa: F401  (re-exported)
    check_share,
    expert_tile_rows,
    expert_ffn,
    expert_shapes,
    gated,
    route,
)


@flax.struct.dataclass
class LatentCarry:
    """Per-lane decode state; every leaf batch-major."""

    cache: tuple[jnp.ndarray, ...]  # a layer: [B, n_prefix + max_len, rank + rope]
    pos: jnp.ndarray                # [B] int32: the position the next token takes
    # [B, expert layers, experts_held + 1] int32: the token-expert
    # assignments the LAST call made for this row on each held expert, and
    # (last column) on all experts; the decode loops tally it (obs counters
    # moe.assignments*), nothing reads it back into the model
    routed: jnp.ndarray


def yarn_inv_freq(cfg: ModelConfig) -> jnp.ndarray:
    """The rope dimensions' rotation frequencies under YaRN: ``theta^(-2i/d)``
    where a dimension turns more than ``beta_fast`` times in the original
    context, that over ``factor`` where fewer than ``beta_slow``, a linear
    ramp between."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    plain = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ys = dict(cfg.rope_scaling)
    if not ys or ys.get("factor", 1) <= 1:
        return plain

    def dim_of(turns):
        return d * math.log(ys["original_max_position_embeddings"]
                            / (turns * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(dim_of(ys["beta_fast"])), 0)
    high = min(math.ceil(dim_of(ys["beta_slow"])), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 0.001), 0.0, 1.0)
    return plain / ys["factor"] * ramp + plain * (1.0 - ramp)


def softmax_scale(cfg: ModelConfig) -> float:
    """``(nope + rope)^-0.5 * m^2`` with YaRN's ``m = 0.1 * mscale_all_dim *
    ln(factor) + 1``; the output scale ``mscale / mscale_all_dim`` on
    cos/sin is 1 in the published config and is not applied."""
    ys = dict(cfg.rope_scaling)
    m = 1.0
    if ys and ys.get("factor", 1) > 1 and ys.get("mscale_all_dim", 0):
        m = 0.1 * ys["mscale_all_dim"] * math.log(ys["factor"]) + 1.0
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5 * m * m


def rope(x: jnp.ndarray, positions: jnp.ndarray, inv_freq: jnp.ndarray):
    """Rotate the pairs ``(i, i + d/2)`` of ``x [..., d]`` at ``positions``
    (the leading axes of ``x`` up to wherever heads begin); float32 inside."""
    angle = positions[..., None].astype(jnp.float32) * inv_freq
    while angle.ndim < x.ndim:
        angle = angle[..., None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def rms_norm(x, weight, eps: float):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


class LatentMoELayer(nn.Module):
    """One block: latent attention, then a dense or a routed-expert FFN."""

    cfg: ModelConfig
    dense: bool

    def setup(self):
        c = self.cfg
        pd = jnp.dtype(c.param_dtype)
        h, H = c.hidden_size, c.num_attention_heads
        w = nn.initializers.normal(c.initializer_range)
        one = nn.initializers.ones
        shapes = {
            "input_layernorm": (one, (h,)),
            "q_a_proj": (w, (h, c.q_lora_rank)),
            "q_a_layernorm": (one, (c.q_lora_rank,)),
            "q_b_proj": (w, (c.q_lora_rank,
                             H * (c.qk_nope_head_dim + c.qk_rope_head_dim))),
            "kv_a_proj_with_mqa": (w, (h, c.kv_lora_rank + c.qk_rope_head_dim)),
            "kv_a_layernorm": (one, (c.kv_lora_rank,)),
            "kv_b_proj": (w, (c.kv_lora_rank,
                              H * (c.qk_nope_head_dim + c.v_head_dim))),
            "o_proj": (w, (H * c.v_head_dim, h)),
            "post_attention_layernorm": (one, (h,)),
        }
        if self.dense:
            m = c.intermediate_size
            shapes.update(gate_proj=(w, (h, m)), up_proj=(w, (h, m)),
                          down_proj=(w, (m, h)))
        else:
            shapes.update(expert_shapes(c, w))
        self.p = {name: self.param(name, init, shape, pd)
                  for name, (init, shape) in shapes.items()}
        if not self.dense:
            # drawn, not zero, so that a bias leaking into the weights shows
            self.bias = self.param(
                "e_score_correction_bias", w, (c.n_routed_experts,), jnp.float32)

    # ---- FFN -----------------------------------------------------------------

    def ffn(self, x, live, differentiable: bool):
        """x [N, h], live [N] -> (out [N, h], tally [N, held + 1] or None)."""
        p = self.p
        if self.dense:
            return gated(x, p["gate_proj"], p["up_proj"], p["down_proj"]), None
        return expert_ffn(self.cfg, p, self.bias, x, live, differentiable)

    # ---- attention -------------------------------------------------------------

    def _queries(self, x, positions):
        """-> (q_nope [..., H, nope], q_rope [..., H, rope] rotated)."""
        c, p, dt = self.cfg, self.p, x.dtype
        c_q = rms_norm(x @ p["q_a_proj"].astype(dt), p["q_a_layernorm"],
                       c.rms_norm_eps)
        q = (c_q @ p["q_b_proj"].astype(dt)).reshape(
            x.shape[:-1] + (c.num_attention_heads,
                            c.qk_nope_head_dim + c.qk_rope_head_dim))
        return (q[..., :c.qk_nope_head_dim],
                rope(q[..., c.qk_nope_head_dim:], positions, yarn_inv_freq(c)))

    def _compressed(self, x, positions):
        """-> ``[c_kv | k_r]`` [..., rank + rope]: what the cache holds."""
        c, p = self.cfg, self.p
        kv = x @ p["kv_a_proj_with_mqa"].astype(x.dtype)
        c_kv = rms_norm(kv[..., :c.kv_lora_rank], p["kv_a_layernorm"],
                        c.rms_norm_eps)
        k_r = rope(kv[..., c.kv_lora_rank:], positions, yarn_inv_freq(c))
        return jnp.concatenate([c_kv, k_r], axis=-1)

    def _kv_b(self, dt):
        c = self.cfg
        w = self.p["kv_b_proj"].astype(dt).reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def attend_full(self, x, positions, mask):
        """Expanded attention over whole sequences: x [B, P, h], positions
        [B, P], mask [B, P, P] (query, key) -> (out [B, P, h], the
        compressed ``[c_kv | k_r]`` [B, P, rank + rope])."""
        c, dt = self.cfg, x.dtype
        B, P, _ = x.shape
        H, rank = c.num_attention_heads, c.kv_lora_rank
        q_nope, q_r = self._queries(x, positions)
        ckv = self._compressed(x, positions)
        wk, wv = self._kv_b(dt)
        k_nope = jnp.einsum("bpc,chd->bphd", ckv[..., :rank], wk)
        v = jnp.einsum("bpc,chd->bphd", ckv[..., :rank], wv)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            ckv[:, :, None, rank:], (B, P, H, c.qk_rope_head_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_r], axis=-1)
        scores = jnp.einsum("bihd,bjhd->bhij", q, k,
                            preferred_element_type=jnp.float32) * softmax_scale(c)
        probs = jax.nn.softmax(jnp.where(mask[:, None], scores, -1.0e30), axis=-1)
        out = jnp.einsum("bhij,bjhd->bihd", probs.astype(dt), v)
        return out.reshape(B, P, H * c.v_head_dim) @ self.p["o_proj"].astype(dt), ckv

    def attend_step(self, x, cache, pos, key_ok):
        """Absorbed attention for one new token a row: x [N, h], cache
        [N, P, rank + rope], pos [N], key_ok [N, P] -> (out [N, h], cache
        with the new position written)."""
        c, dt = self.cfg, x.dtype
        N, P, _ = cache.shape
        rank = c.kv_lora_rank
        q_nope, q_r = self._queries(x, pos)
        cache = cache.at[jnp.arange(N), pos].set(
            self._compressed(x, pos).astype(cache.dtype))
        wk, wv = self._kv_b(dt)
        q = jnp.concatenate([jnp.einsum("nhd,chd->nhc", q_nope, wk), q_r], -1)
        scores = jnp.einsum("nhc,npc->nhp", q, cache.astype(dt),
                            preferred_element_type=jnp.float32) * softmax_scale(c)
        seen = (jnp.arange(P)[None, :] <= pos[:, None]) & key_ok
        probs = jax.nn.softmax(jnp.where(seen[:, None], scores, -1.0e30), axis=-1)
        ctx = jnp.einsum("nhp,npc->nhc", probs.astype(dt),
                         cache[..., :rank].astype(dt))
        out = jnp.einsum("nhc,chd->nhd", ctx, wv).reshape(
            N, c.num_attention_heads * c.v_head_dim)
        return out @ self.p["o_proj"].astype(dt), cache

    # ---- the block ---------------------------------------------------------------

    def full(self, x, positions, mask, live, differentiable: bool,
             with_ffn: bool = True):
        """x [B, P, h] -> (x, compressed [B, P, rank + rope], tally)."""
        c, p = self.cfg, self.p
        a, ckv = self.attend_full(
            rms_norm(x, p["input_layernorm"], c.rms_norm_eps), positions, mask)
        x = x + a
        if not with_ffn:
            return x, ckv, None
        B, P, h = x.shape
        y, tally = self.ffn(
            rms_norm(x, p["post_attention_layernorm"], c.rms_norm_eps
                     ).reshape(B * P, h), live.reshape(B * P), differentiable)
        if tally is not None:
            tally = tally.reshape(B, P, -1).sum(axis=1)
        return x + y.reshape(B, P, h), ckv, tally

    def step(self, x, cache, pos, key_ok):
        """x [N, h] -> (x, cache, tally [N, held + 1] or None)."""
        c, p = self.cfg, self.p
        a, cache = self.attend_step(
            rms_norm(x, p["input_layernorm"], c.rms_norm_eps), cache, pos, key_ok)
        x = x + a
        y, tally = self.ffn(
            rms_norm(x, p["post_attention_layernorm"], c.rms_norm_eps),
            jnp.ones(x.shape[:1], bool), differentiable=False)
        return x + y, cache, tally


class LatentMoEDecoder(nn.Module):
    """Prefix projector, the stack, final norm and head."""

    cfg: ModelConfig

    def setup(self):
        c = self.cfg
        check_share(c)
        if c.qk_rope_head_dim % 2 or c.num_hidden_layers < 1:
            raise ValueError("qk_rope_head_dim must be even and "
                             "num_hidden_layers >= 1")
        pd = jnp.dtype(c.param_dtype)
        w = nn.initializers.normal(c.initializer_range)
        self.embed = {name: self.param(f"embed_{name}", w, (dim, c.hidden_size), pd)
                      for name, dim in c.modalities}
        self.embed_tokens = self.param(
            "embed_tokens", w, (c.vocab_size, c.hidden_size), pd)
        self.layers = [
            LatentMoELayer(c, dense=i < c.first_k_dense_replace,
                           name=f"layers_{i}")
            for i in range(c.num_hidden_layers)]
        self.norm = self.param("norm", nn.initializers.ones, (c.hidden_size,), pd)
        self.lm_head = self.param("lm_head", w, (c.hidden_size, c.vocab_size), pd)

    @property
    def n_prefix(self) -> int:
        return len(self.cfg.modalities) * self.cfg.max_frames

    def _prefix(self, feats, masks):
        """-> (x [B, n_prefix, h], valid [B, n_prefix] float32)."""
        dt = jnp.dtype(self.cfg.dtype)
        names = self.cfg.modality_names
        valid = jnp.concatenate(
            [masks[n].astype(jnp.float32) for n in names], axis=1)
        x = jnp.concatenate(
            [feats[n].astype(dt) @ self.embed[n].astype(dt) for n in names], 1)
        return x * valid[..., None].astype(dt), valid

    def _tallies(self, tallies, batch: int):
        """Per-layer tallies ([B, held + 1] or None) -> [B, expert layers,
        held + 1] int32, zeros for a layer that did not run its FFN."""
        c = self.cfg
        zero = jnp.zeros((batch, c.experts_held + 1), jnp.int32)
        moe = [zero if t is None else t
               for layer, t in zip(self.layers, tallies) if not layer.dense]
        return (jnp.stack(moe, axis=1) if moe
                else jnp.zeros((batch, 0, c.experts_held + 1), jnp.int32))

    def _logits(self, x):
        x = rms_norm(x, self.norm, self.cfg.rms_norm_eps)
        return jnp.dot(x, self.lm_head.astype(x.dtype),
                       preferred_element_type=jnp.float32)

    def _init_only(self):
        """Under ``init`` the parameters are all that is wanted (every
        caller takes the tree as a template or as seeded weights): make each
        layer declare its own and run no forward, which at the published
        widths would be a few hundred eager dispatches over 10 GB."""
        for layer in self.layers:
            layer.p

    def prefill(self, feats, masks):
        """-> (valid [B, n_prefix], LatentCarry): the prefix through the
        stack, each layer's compressed keys written into a cache of
        ``n_prefix + max_len`` positions. The last layer's FFN over the
        prefix feeds nothing and is not run."""
        c = self.cfg
        x, valid = self._prefix(feats, masks)
        B, P, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(P), (B, P))
        ok = valid > 0
        mask = (jnp.arange(P)[:, None] >= jnp.arange(P)[None, :]) & ok[:, None, :]
        caches, tallies = [], []
        for i, layer in enumerate(self.layers):
            x, ckv, tally = layer.full(
                x, positions, mask, ok, differentiable=False,
                with_ffn=i + 1 < len(self.layers))
            caches.append(jnp.pad(ckv, ((0, 0), (0, c.max_len), (0, 0))))
            tallies.append(tally)
        return valid, LatentCarry(
            tuple(caches), jnp.full((B,), P, jnp.int32), self._tallies(tallies, B))

    def step(self, carry: LatentCarry, token, valid):
        """One token a row through the cache -> (carry, logits [N, V] f32)."""
        dt = jnp.dtype(self.cfg.dtype)
        x = self.embed_tokens.astype(dt)[token]
        N = x.shape[0]
        key_ok = jnp.concatenate(
            [valid > 0, jnp.ones((N, self.cfg.max_len), bool)], axis=1)
        caches, tallies = [], []
        for layer, cache in zip(self.layers, carry.cache):
            x, cache, tally = layer.step(x, cache, carry.pos, key_ok)
            caches.append(cache)
            tallies.append(tally)
        return LatentCarry(tuple(caches), carry.pos + 1,
                           self._tallies(tallies, N)), self._logits(x)

    def __call__(self, feats, masks, labels):
        """Teacher forcing: the stack over prefix + shifted caption, causal
        -> logits [B, T, V] float32; ``logits[:, t]`` predicts ``labels[:, t]``."""
        c = self.cfg
        B, T = labels.shape
        if self.is_initializing():
            self._init_only()
            # logits are float32 on every path
            return jnp.zeros((B, T, c.vocab_size), jnp.float32)  # graftlint: disable=GL005
        dt = jnp.dtype(c.dtype)
        prefix, valid = self._prefix(feats, masks)
        inputs = jnp.concatenate(
            [jnp.full((B, 1), BOS_ID, labels.dtype), labels[:, :-1]], axis=1)
        x = jnp.concatenate([prefix, self.embed_tokens.astype(dt)[inputs]], 1)
        P = x.shape[1]
        positions = jnp.broadcast_to(jnp.arange(P), (B, P))
        ok = jnp.concatenate([valid > 0, jnp.ones((B, T), bool)], axis=1)
        mask = (jnp.arange(P)[:, None] >= jnp.arange(P)[None, :]) & ok[:, None, :]
        for layer in self.layers:
            x, _, _ = layer.full(x, positions, mask, ok, differentiable=True)
        return self._logits(x[:, self.n_prefix:])
