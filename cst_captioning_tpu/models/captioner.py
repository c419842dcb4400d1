"""CaptionModel: encoder + LSTM decoder with shared single-step semantics.

The reference's ``CaptionModel`` couples ``forward`` (teacher forcing) and
``sample`` (greedy/multinomial/beam) in one torch module (SURVEY.md §2 row 4).
Here the same capability is split TPU-style:

- :meth:`encode`       — one pass building the memory bank + initial carry,
- :meth:`decode_step`  — one decoder step (used by every decoding strategy),
- :meth:`__call__`     — teacher-forced unroll of ``decode_step`` via
  ``nn.scan`` (compiled to a single fused XLA while loop; no per-step Python).

Teacher forcing and all samplers therefore share parameters *and* code, which
is what makes the unroll-consistency test (SURVEY.md §4 item 2) meaningful.
"""

from __future__ import annotations

import functools

import flax.linen as nn
import flax.struct
import jax
import jax.numpy as jnp

from cst_captioning_tpu.config.config import BOS_ID, ModelConfig
from cst_captioning_tpu.models.decoder import Carry, DecoderCell
from cst_captioning_tpu.models.latent_moe import LatentMoEDecoder
from cst_captioning_tpu.models.encoders import (
    MeanPoolEncoder,
    TemporalAttentionEncoder,
    masked_mean,
)


@flax.struct.dataclass
class EncoderOutput:
    memory: jnp.ndarray       # [B, M, E]
    memory_proj: jnp.ndarray  # [B, M, d_att] attention key projection
    memory_mask: jnp.ndarray  # [B, M]
    carry: Carry              # initial LSTM carry

    def take_batch(self, idx: jnp.ndarray) -> "EncoderOutput":
        """Gather batch rows ``idx`` from every leaf (all are batch-major).

        The fused decode's finished-lane compaction permutes still-active
        batch columns into a dense prefix between strides
        (decoding/fused.py); the encoder output must follow the same
        permutation so each row keeps attending over its own memory bank.
        """
        return jax.tree.map(lambda x: jnp.take(x, idx, axis=0), self)


def shift_right(labels: jnp.ndarray) -> jnp.ndarray:
    """[B, T] target tokens -> decoder inputs [B, T] starting with BOS."""
    bos = jnp.full((labels.shape[0], 1), BOS_ID, dtype=labels.dtype)
    return jnp.concatenate([bos, labels[:, :-1]], axis=1)


def _scan_step(mdl, carry, token, memory, memory_proj, memory_mask, deterministic):
    return mdl.cell(carry, token, memory, memory_proj, memory_mask, deterministic)


def _scan_step_logp(mdl, carry, tokens, memory, memory_proj, memory_mask,
                    deterministic):
    """One teacher-forced step emitting only the TARGET token's logprob.

    The per-step ``[B, V]`` logits are consumed immediately (logsumexp +
    gather fuse into the step), so the ``[B, T, V]`` stack never reaches
    HBM — the point of :meth:`CaptionModel.teacher_force_logps`. Shares
    ``selected_logprob`` with the decode loops: the REINFORCE logprobs and
    the decode-time logprobs are the same association order by construction.
    """
    from cst_captioning_tpu.decoding.common import selected_logprob

    token_in, token_tgt = tokens
    carry, logits = mdl.cell(
        carry, token_in, memory, memory_proj, memory_mask, deterministic
    )
    return carry, selected_logprob(logits.astype(jnp.float32), token_tgt)


class CaptionModel(nn.Module):
    cfg: ModelConfig

    def setup(self):
        cfg = self.cfg
        if cfg.decoder == "latent_moe":
            # the second decoder kind (models/latent_moe.py): the frame
            # embedding is the projector of a video prefix and the state is
            # a compressed attention cache; nothing of the LSTM is built
            self.decoder = LatentMoEDecoder(cfg, name="decoder")
            return
        if cfg.encoder == "meanpool":
            self.encoder = MeanPoolEncoder(cfg, name="encoder")
        else:
            self.encoder = TemporalAttentionEncoder(cfg, name="encoder")
        self.cell = DecoderCell(cfg, name="cell")
        dtype = jnp.dtype(cfg.dtype)
        pdtype = jnp.dtype(cfg.param_dtype)
        # LSTM carry is initialized from the pooled memory (the reference
        # instead feeds the video feature at step 0 — same information path,
        # but this keeps step 0 identical to every other step for the scan)
        self.init_c = [
            nn.Dense(cfg.d_hidden, name=f"init_c{i}", dtype=dtype, param_dtype=pdtype)
            for i in range(cfg.num_layers)
        ]
        self.init_h = [
            nn.Dense(cfg.d_hidden, name=f"init_h{i}", dtype=dtype, param_dtype=pdtype)
            for i in range(cfg.num_layers)
        ]

    # ---- encoding ----------------------------------------------------------

    def encode(
        self, feats: dict[str, jnp.ndarray], masks: dict[str, jnp.ndarray]
    ) -> EncoderOutput:
        if self.cfg.decoder == "latent_moe":
            # the prefill: the bank is the cache riding in ``carry``; the
            # prefix mask is the memory mask, the other two fields are empty
            valid, carry = self.decoder.prefill(feats, masks)
            none = jnp.zeros((valid.shape[0], 0), jnp.dtype(self.cfg.dtype))
            return EncoderOutput(none, none, valid, carry)
        memory, mmask = self.encoder(feats, masks)
        memory_proj = self.cell.project_memory(memory)
        ctx0 = masked_mean(memory, mmask, axis=1, axis_name=self.cfg.seq_axis)
        carry = tuple(
            (jnp.tanh(self.init_c[i](ctx0)), jnp.tanh(self.init_h[i](ctx0)))
            for i in range(self.cfg.num_layers)
        )
        return EncoderOutput(memory, memory_proj, mmask, carry)

    # ---- single step (greedy / sampling / beam all call this) ---------------

    def decode_step(
        self,
        carry: Carry,
        token: jnp.ndarray,
        enc: EncoderOutput,
        deterministic: bool = True,
    ) -> tuple[Carry, jnp.ndarray]:
        if self.cfg.decoder == "latent_moe":
            return self.decoder.step(carry, token, enc.memory_mask)
        return self.cell(
            carry, token, enc.memory, enc.memory_proj, enc.memory_mask, deterministic
        )

    # ---- teacher forcing -----------------------------------------------------

    def _lstm_only(self, what: str) -> None:
        if self.cfg.decoder != "lstm":
            raise NotImplementedError(
                f"CaptionModel.{what} unrolls the LSTM cell from an encoder "
                f"pass; decoder={self.cfg.decoder!r} teacher-forces through "
                "__call__ (the REINFORCE update that tiles one encoder pass "
                "over K rollouts, rl/scst.py, has no such path yet)"
            )

    def decode_logits(
        self,
        enc: EncoderOutput,
        labels: jnp.ndarray,
        train: bool = False,
    ) -> jnp.ndarray:
        """Teacher-forced unroll from an already-built :class:`EncoderOutput`.

        Split from :meth:`__call__` so callers that reuse one encoder pass
        for many label rows (the REINFORCE update teacher-forces K rollouts
        per clip against TILED memory — rl/scst.py) pay the encoder once
        instead of per row."""
        self._lstm_only("decode_logits")
        inputs = shift_right(labels)
        scan = nn.scan(
            functools.partial(_scan_step, deterministic=not train),
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=(1, nn.broadcast, nn.broadcast, nn.broadcast),
            out_axes=1,
        )
        _, logits = scan(
            self, enc.carry, inputs, enc.memory, enc.memory_proj, enc.memory_mask
        )
        return logits

    def teacher_force_logps(
        self,
        enc: EncoderOutput,
        labels: jnp.ndarray,
        train: bool = False,
    ) -> jnp.ndarray:
        """Per-position logprob of ``labels`` under teacher forcing: [B, T].

        Equals ``sequence_log_probs(decode_logits(enc, labels), labels)``
        (pinned by test) but never materializes the ``[B, T, V]`` logits
        stack — at the flagship dims that array is ~2 GB of f32 per REINFORCE
        chunk whose only use is a gather + logsumexp, pure HBM traffic the
        in-scan form avoids (rl/scst.py's update path)."""
        self._lstm_only("teacher_force_logps")
        inputs = shift_right(labels)
        scan = nn.scan(
            functools.partial(_scan_step_logp, deterministic=not train),
            variable_broadcast="params",
            split_rngs={"params": False, "dropout": True},
            in_axes=((1, 1), nn.broadcast, nn.broadcast, nn.broadcast),
            out_axes=1,
        )
        _, logps = scan(
            self, enc.carry, (inputs, labels), enc.memory, enc.memory_proj,
            enc.memory_mask,
        )
        return logps

    def __call__(
        self,
        feats: dict[str, jnp.ndarray],
        masks: dict[str, jnp.ndarray],
        labels: jnp.ndarray,
        train: bool = False,
    ) -> jnp.ndarray:
        """-> logits [B, T, V] (f32); logits[:, t] predicts labels[:, t]."""
        if self.cfg.decoder == "latent_moe":
            return self.decoder(feats, masks, labels)
        return self.decode_logits(self.encode(feats, masks), labels, train)
