"""LSTM decoder cell with input-feed attention context.

One step of the caption decoder (reference ``model.py`` decode loop,
SURVEY.md §2 row 4): embed the previous token, attend over the encoder memory
with the previous top-layer hidden state, feed ``[word_emb, context]`` through
the LSTM stack, project to vocab logits. Written as a single-step module so
teacher forcing (``nn.scan``), greedy/multinomial sampling and beam search all
share the exact same parameters and code path.

The step is in two parts: :meth:`DecoderCell.embed` looks the token up and
:meth:`DecoderCell.step` runs everything after the lookup on the embedded
row. ``__call__`` is the one after the other, which is what every decode
loop, the beam search and ``decode_logits`` run. The RL update's teacher
forcing (``models/captioner.py::_bounded_logps``) knows all its input
tokens before its loop starts, so it calls ``embed`` once for all positions
and ``step`` in the loop: no position of it touches the ``[V, d_embed]``
table, forward or backward.

``ops/decode_pallas.py`` reimplements exactly this step (minus dropout —
decode is deterministic) as one fused TPU kernel over this module's
parameter tree, selected by ``ModelConfig.decode_impl``; any change to the
math here must be mirrored there (the parity sweep in
tests/test_ops_decode_pallas.py pins the two together).
"""

from __future__ import annotations

import flax.linen as nn
import jax.numpy as jnp

from cst_captioning_tpu.config.config import ModelConfig
from cst_captioning_tpu.models.attention import AdditiveAttention

# carry: tuple over layers of LSTM (c, h) pairs
Carry = tuple[tuple[jnp.ndarray, jnp.ndarray], ...]

# flax OptimizedLSTMCell parameter families, in the order its concatenated
# gate matmul splits them: i (input), f (forget), g (cell), o (output).
# ops/decode_pallas.py concatenates the per-gate kernels in EXACTLY this
# order when it rebuilds the cell's gate matmul inside the fused decode-step
# kernel — keep the two in lockstep.
LSTM_GATE_ORDER = ("i", "f", "g", "o")


class DecoderCell(nn.Module):
    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        self.word_embed = nn.Embed(
            cfg.vocab_size, cfg.d_embed, name="word_embed",
            dtype=dtype, param_dtype=pdtype,
        )
        self.attention = AdditiveAttention(
            d_att=cfg.d_att, dtype=dtype, param_dtype=pdtype, name="attention",
            seq_axis=cfg.seq_axis,
        )
        self.lstm = [
            nn.OptimizedLSTMCell(
                cfg.d_hidden, dtype=dtype, param_dtype=pdtype, name=f"lstm{i}"
            )
            for i in range(cfg.num_layers)
        ]
        self.out_proj = nn.Dense(
            cfg.vocab_size, name="out_proj", dtype=dtype, param_dtype=pdtype
        )
        self.dropout = nn.Dropout(rate=cfg.dropout)

    def project_memory(self, memory: jnp.ndarray) -> jnp.ndarray:
        return self.attention.project_memory(memory)

    def embed(self, token: jnp.ndarray) -> jnp.ndarray:
        """int32 tokens ``[...]`` -> their rows of the word embedding
        ``[..., d_embed]`` in the compute dtype."""
        return self.word_embed(token)

    def step(
        self,
        carry: Carry,
        embedded: jnp.ndarray,     # [B, d_embed] :meth:`embed` of the token
        memory: jnp.ndarray,       # [B, M, E]
        memory_proj: jnp.ndarray,  # [B, M, d_att]
        memory_mask: jnp.ndarray,  # [B, M]
        deterministic: bool = True,
    ) -> tuple[Carry, jnp.ndarray]:
        """One decode step from the embedded previous token -> (new carry,
        logits [B, V] float32)."""
        h_top = carry[-1][1]
        ctx = self.attention(h_top, memory, memory_proj, memory_mask)
        x = jnp.concatenate([embedded, ctx], axis=-1)
        x = self.dropout(x, deterministic=deterministic)
        new_carry = []
        for i, cell in enumerate(self.lstm):
            c_i, x = cell(carry[i], x)
            new_carry.append(c_i)
            if i + 1 < len(self.lstm):
                x = self.dropout(x, deterministic=deterministic)
        x = self.dropout(x, deterministic=deterministic)
        # logits in f32: softmax/loss stability is worth the cast
        logits = self.out_proj(x).astype(jnp.float32)
        return tuple(new_carry), logits

    def __call__(
        self,
        carry: Carry,
        token: jnp.ndarray,        # [B] int32 previous token
        memory: jnp.ndarray,       # [B, M, E]
        memory_proj: jnp.ndarray,  # [B, M, d_att]
        memory_mask: jnp.ndarray,  # [B, M]
        deterministic: bool = True,
    ) -> tuple[Carry, jnp.ndarray]:
        """One decode step -> (new carry, logits [B, V] float32)."""
        return self.step(
            carry, self.embed(token), memory, memory_proj, memory_mask,
            deterministic,
        )
