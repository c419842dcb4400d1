"""Additive (Bahdanau) temporal attention over a memory bank.

The reference's temporal attention scores each frame against the decoder
state with ``v^T tanh(W_f f + W_h h)`` (CST paper §3.1 / SURVEY.md §5). Here
the memory projection ``W_f f`` is precomputed once per sequence by the
encoder (it does not depend on the step), so the per-step cost is one small
matmul + a masked softmax — XLA fuses the whole step into a couple of kernels.

Sequence parallelism (``seq_axis`` set): the memory bank arrives FRAME-SHARDED
across the mesh axis and the softmax becomes a two-pass distributed reduction
— ``pmax`` of the local score maxima, then one ``psum`` of the (numerator,
denominator) pair, the "one-step ring" of SURVEY.md §5's long-context row.
Attention is permutation-invariant over memory slots, so sharded results
equal the single-device softmax exactly (up to f32 summation order).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp


class AdditiveAttention(nn.Module):
    d_att: int
    dtype: jnp.dtype = jnp.float32
    param_dtype: jnp.dtype = jnp.float32
    # mesh axis the frame dimension is sharded over ("" = not sharded)
    seq_axis: str = ""

    def setup(self):
        self.mem_proj = nn.Dense(
            self.d_att, name="mem_proj", use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        self.query_proj = nn.Dense(
            self.d_att, name="query_proj", use_bias=True,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )
        self.score = nn.Dense(
            1, name="score", use_bias=False,
            dtype=self.dtype, param_dtype=self.param_dtype,
        )

    def project_memory(self, memory: jnp.ndarray) -> jnp.ndarray:
        """[B, M, E] -> [B, M, d_att]; hoisted out of the decode loop."""
        return self.mem_proj(memory)

    def __call__(
        self,
        query: jnp.ndarray,        # [B, H] decoder state
        memory: jnp.ndarray,       # [B, M, E] value bank
        memory_proj: jnp.ndarray,  # [B, M, d_att] = project_memory(memory)
        memory_mask: jnp.ndarray,  # [B, M] 1/0
    ) -> jnp.ndarray:
        """-> context [B, E]: mask-weighted sum of memory slots."""
        q = self.query_proj(query)
        scores = self.score(jnp.tanh(memory_proj + q[:, None, :]))[..., 0]  # [B, M]
        # -1e9, not -inf: a row with zero valid slots must yield a finite
        # (uniform) softmax over zeroed memory, not NaNs that poison the step
        scores = jnp.where(memory_mask > 0, scores, -1.0e9)
        if self.seq_axis:
            return self._sharded_softmax_attend(scores, memory)
        # softmax in f32 for stability regardless of compute dtype
        weights = nn.softmax(scores.astype(jnp.float32), axis=-1).astype(memory.dtype)
        return jnp.einsum("bm,bme->be", weights, memory)

    def _sharded_softmax_attend(
        self, scores: jnp.ndarray, memory: jnp.ndarray
    ) -> jnp.ndarray:
        """Distributed masked softmax over the frame-sharded memory axis."""
        s = scores.astype(jnp.float32)                         # [B, M_local]
        # global max is a constant shift for softmax — stop_gradient both
        # keeps the math exact and sidesteps pmax's missing diff rule
        m = jax.lax.pmax(
            jax.lax.stop_gradient(jnp.max(s, axis=-1)), self.seq_axis
        )                                                      # [B] global max
        w = jnp.exp(s - m[:, None])
        den = jax.lax.psum(jnp.sum(w, axis=-1), self.seq_axis)              # [B]
        num = jax.lax.psum(
            jnp.einsum("bm,bme->be", w.astype(memory.dtype), memory)
            .astype(jnp.float32),
            self.seq_axis,
        )                                                      # [B, E]
        return (num / den[:, None]).astype(memory.dtype)
