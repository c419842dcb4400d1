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
from cst_captioning_tpu.models.cca_moe import CCAMoEDecoder
from cst_captioning_tpu.models.decoder import Carry, DecoderCell
from cst_captioning_tpu.models.eva import EvaDecoder
from cst_captioning_tpu.models.latent_moe import LatentMoEDecoder
from cst_captioning_tpu.models.sparse_linear import SparseLinearDecoder
from cst_captioning_tpu.models.window_moe import WindowMoEDecoder
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


def scan_positions(labels: jnp.ndarray) -> jnp.ndarray:
    """int32 ``[positions run, positions]`` of
    :meth:`CaptionModel.teacher_force_logps` over ``labels`` [B, T]: the
    batch's depth (``decoding.common.caption_depth``), of T."""
    from cst_captioning_tpu.decoding.common import caption_depth

    return jnp.stack([caption_depth(labels), jnp.int32(labels.shape[-1])])


def _bounded_logps(cell, params, carry, bank, inputs, targets, depth):
    """Teacher forcing over the first ``depth`` of T positions: the target
    tokens' logprobs ``[T, B]`` (0.0 from ``depth`` on) of the unbound
    ``cell`` under ``params``, from ``inputs`` / ``targets`` ``[T, B]`` and
    the ``bank`` every position attends over (``memory``, ``memory_proj``,
    ``memory_mask``).

    ``depth`` is a traced scalar and both passes are loops whose trip count
    it is (``fori_loop(0, depth)``: the count is known before the loop
    starts and compared with the loop's own counter, so no iteration waits
    for a predicate computed from vector data), which reverse-mode
    differentiation cannot walk by itself: the backward pass is written
    here. The forward pass keeps each position's incoming carry, ``[T, B,
    d]`` a layer, and nothing else; the backward pass walks ``depth - 1 ..
    0`` and differentiates each position's step where it stands, forward
    again from the kept carry, adding the parameters' and the bank's
    cotangents in the order a reversed ``scan`` adds them. So a position
    past the depth costs nothing in either pass, and a step's ``[B, V]``
    logits live inside the step in both: no ``[T, B, V]`` residual is
    written to HBM and read back (the scan this replaced kept one in f32;
    PERF.md section 5). A step shares ``selected_logprob`` with the decode
    loops: the REINFORCE logprobs and the decode-time logprobs are the same
    association order by construction.

    The word embedding is outside both loops. Every input token is known
    before the first position runs, so all ``T x B`` rows are looked up once
    (``DecoderCell.embed``, ``[T, B, d_embed]`` in the compute dtype; kept
    for the backward pass) and a position's step (``DecoderCell.step``)
    reads its ``[B, d_embed]`` slice. The loops see the parameter tree
    without the ``word_embed`` leaf. The backward loop writes each
    position's ``[B, d_embed]`` input cotangent into a ``[T, B, d_embed]``
    buffer (zero from ``depth`` on), and after it the table's gradient is
    their sum by input token, once, every addition in f32
    (:func:`_rows_by_token`). No position makes, converts or adds a ``[V,
    d_embed]`` array.

    Under ``shard_map`` each shard runs its own rows to its own depth, so no
    iteration of either loop may hold a collective over a mesh axis the
    tokens vary over. A parameter that is the same on every shard of such
    an axis (parallel/seq_parallel.py, which differentiates outside the
    map) is therefore typed varying before the loops: its cotangent is
    summed over the axis by that cast's transpose, once, after the backward
    loop, where jax would otherwise sum it a position inside it.
    """
    from cst_captioning_tpu.decoding.common import selected_logprob

    def local(x):
        over = tuple(jax.typeof(inputs).vma - jax.typeof(x).vma)
        return jax.lax.pcast(x, over, to="varying") if over else x

    params = jax.tree.map(local, params)
    table = {"word_embed": params["word_embed"]}
    rest = {k: v for k, v in params.items() if k != "word_embed"}

    def step(p, carry, embedded, bank, targets, t):
        carry, logits = cell.apply(
            {"params": p}, carry, embedded, *bank, method=DecoderCell.step
        )
        return carry, selected_logprob(logits.astype(jnp.float32), targets[t])

    def put(buffer, x, t):
        return jax.lax.dynamic_update_index_in_dim(buffer, x, t, 0)

    def forward(table, p, carry, bank, tokens, depth):
        inputs, targets = tokens
        embedded = cell.apply(
            {"params": table}, inputs, method=DecoderCell.embed
        )

        def body(t, loop):
            carry, carries, logps = loop
            carries = jax.tree.map(lambda b, c: put(b, c, t), carries, carry)
            carry, logp = step(p, carry, embedded[t], bank, targets, t)
            return carry, carries, put(logps, logp, t)

        # zeros made from the operands: inside shard_map they vary over the
        # mesh axes the values written into them vary over
        T = inputs.shape[:1]
        carries = jax.tree.map(
            lambda c: jnp.zeros_like(c, shape=T + c.shape), carry
        )
        logps = jnp.zeros_like(carry[0][0], jnp.float32, shape=inputs.shape)
        _, carries, logps = jax.lax.fori_loop(
            0, depth, body, (carry, carries, logps)
        )
        return logps, (carries, embedded)

    # the tokens and the depth are arguments and not closed over: where the
    # gradient is taken outside shard_map (parallel/seq_parallel.py) the
    # backward pass is traced apart from the forward pass
    @jax.custom_vjp
    def run(table, p, carry, bank, tokens, depth):
        return forward(table, p, carry, bank, tokens, depth)[0]

    def run_fwd(table, p, carry, bank, tokens, depth):
        logps, (carries, embedded) = forward(
            table, p, carry, bank, tokens, depth
        )
        return logps, (table, p, carries, embedded, bank, tokens, depth)

    def run_bwd(kept, d_logps):
        table, p, carries, embedded, bank, tokens, depth = kept
        inputs, targets = tokens

        def body(i, loop):
            t = depth - 1 - i
            d_carry, d_embedded, d_rest = loop
            _, step_vjp = jax.vjp(
                lambda *args: step(*args, targets, t),
                p, jax.tree.map(lambda b: b[t], carries), embedded[t], bank,
            )
            g_p, d_carry, g_embedded, g_bank = step_vjp((d_carry, d_logps[t]))
            return (
                d_carry, put(d_embedded, g_embedded, t),
                jax.tree.map(jnp.add, d_rest, (g_p, g_bank)),
            )

        zeros = lambda tree: jax.tree.map(jnp.zeros_like, tree)  # noqa: E731
        d_carry, d_embedded, (d_p, d_bank) = jax.lax.fori_loop(
            0, depth, body,
            (zeros(jax.tree.map(lambda b: b[0], carries)), zeros(embedded),
             zeros((p, bank))),
        )
        d_table = jax.tree.map(
            lambda leaf: _rows_by_token(inputs, d_embedded, leaf), table
        )
        return d_table, d_p, d_carry, d_bank, None, None   # integers: none

    run.defvjp(run_fwd, run_bwd)
    return run(table, rest, carry, bank, (inputs, targets), depth)


def _rows_by_token(tokens, rows, table):
    """The cotangent of ``table`` ``[V, d]`` under ``table[tokens]``: the
    sums of ``rows`` ``[..., d]`` by their token ``[...]``, every addition
    in f32, in the table's dtype. One scatter-add of all the rows (of this
    and the one-hot product ``scripts/update_row_sweep.py`` keeps to measure
    against it, the faster on the chip: PERF.md section 6, PR 41)."""
    summed = jnp.zeros_like(table, jnp.float32).at[tokens.reshape(-1)].add(
        rows.reshape(-1, rows.shape[-1]).astype(jnp.float32)
    )
    return summed.astype(table.dtype)


# the decoder kinds whose step takes all lanes at once (``decode_lanes``);
# decoding/common.py ``lane_decode_step`` vmaps ``decode_step`` for the others
ALL_LANES = ("window_moe", "cca_moe")


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
        if cfg.decoder == "sparse_linear":
            # the third (models/sparse_linear.py): sparse-softmax and linear-
            # attention layers over a long video prefix
            self.decoder = SparseLinearDecoder(cfg, name="decoder")
            return
        if cfg.decoder == "eva":
            # the fourth (models/eva.py): EVA attention over a long video
            # prefix, exact keys in a window and a summary a chunk before it
            self.decoder = EvaDecoder(cfg, name="decoder")
            return
        if cfg.decoder == "window_moe":
            # the fifth (models/window_moe.py): window and full grouped-query
            # attention over a long video prefix, routed experts behind it
            self.decoder = WindowMoEDecoder(cfg, name="decoder")
            return
        if cfg.decoder == "cca_moe":
            # the sixth (models/cca_moe.py): attention in a compressed,
            # convolution-mixed latent over a long video prefix, one expert
            # or none behind an MLP router, the head tied to the embedding
            self.decoder = CCAMoEDecoder(cfg, name="decoder")
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
        if self.cfg.decoder == "sparse_linear":
            # what a clip's lanes share rides where the memory bank does: the
            # sparse layers' prefix keys and values (``memory``) and their
            # compressed keys (``memory_proj``), the mask saying how many of
            # the compacted slots exist; what a lane owns is the carry
            (keys, values, pooled), n, carry = self.decoder.prefill(feats, masks)
            return EncoderOutput((keys, values), pooled, self._live(n), carry)
        if self.cfg.decoder == "eva":
            # likewise: every layer's chunk summaries (``memory``) and the
            # exact keys of the window the prefix ends in (``memory_proj``)
            # are the clip's, held once; the carry is a lane's
            (summaries, near), n, carry = self.decoder.prefill(feats, masks)
            return EncoderOutput(summaries, near, self._live(n), carry)
        if self.cfg.decoder == "window_moe":
            # likewise: every layer's prefix keys and values (``memory``: a
            # full layer's whole, a window layer's last window) from where
            # each clip's window slice starts (``memory_proj``)
            (keys, values, start), n, carry = self.decoder.prefill(feats, masks)
            return EncoderOutput((keys, values), start, self._live(n), carry)
        if self.cfg.decoder == "cca_moe":
            # likewise: every layer's prefix keys and values in the latent
            # (``memory``); the convolution tail the prefix's last position
            # leaves rides in the carry a clip hands its lanes
            bank, n, carry = self.decoder.prefill(feats, masks)
            none = jnp.zeros((n.shape[0], 0), jnp.dtype(self.cfg.dtype))
            return EncoderOutput(bank, none, self._live(n), carry)
        memory, mmask = self.encoder(feats, masks)
        memory_proj = self.cell.project_memory(memory)
        ctx0 = masked_mean(memory, mmask, axis=1, axis_name=self.cfg.seq_axis)
        carry = tuple(
            (jnp.tanh(self.init_c[i](ctx0)), jnp.tanh(self.init_h[i](ctx0)))
            for i in range(self.cfg.num_layers)
        )
        return EncoderOutput(memory, memory_proj, mmask, carry)

    def _live(self, n: jnp.ndarray) -> jnp.ndarray:
        """The memory mask of a prefix whose valid slots are compacted to
        the front: [B, slots] float32, 1 on each row's first ``n``."""
        slots = len(self.cfg.modalities) * self.cfg.max_frames
        return (jnp.arange(slots)[None, :] < n[:, None]).astype(jnp.float32)

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
        if self.cfg.decoder == "sparse_linear":
            return self.decoder.step(
                carry, token, (*enc.memory, enc.memory_proj),
                enc.memory_mask.sum(axis=-1).astype(jnp.int32))
        if self.cfg.decoder == "eva":
            return self.decoder.step(
                carry, token, (enc.memory, enc.memory_proj),
                enc.memory_mask.sum(axis=-1).astype(jnp.int32))
        if self.cfg.decoder == "window_moe":
            return self.decoder.step(
                carry, token, (*enc.memory, enc.memory_proj),
                enc.memory_mask.sum(axis=-1).astype(jnp.int32))
        if self.cfg.decoder == "cca_moe":
            return self.decoder.step(
                carry, token, enc.memory,
                enc.memory_mask.sum(axis=-1).astype(jnp.int32))
        return self.cell(
            carry, token, enc.memory, enc.memory_proj, enc.memory_mask, deterministic
        )

    def decode_lanes(self, carry, token: jnp.ndarray, enc: EncoderOutput):
        """One step for every lane at once, of a decoder kind that takes it
        so (``ALL_LANES``): carry leaves ``[G, B, ...]``, token [G, B], the
        encoder output once a clip -> (carry, logits [G, B, V]). Where
        :meth:`decode_step` vmapped a lane would run the rows of each lane
        apart, this runs ``G x B`` rows as one list (the routed experts'
        walk) and attends grouped by clip over the keys the lanes share."""
        bank = enc.memory if self.cfg.decoder == "cca_moe" \
            else (*enc.memory, enc.memory_proj)
        return self.decoder.step_lanes(
            carry, token, bank, enc.memory_mask.sum(axis=-1).astype(jnp.int32))

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
    ) -> jnp.ndarray:
        """Per-position logprob of ``labels`` under teacher forcing, without
        dropout: [B, T], 0.0 from the batch's depth on.

        Equals ``sequence_log_probs(decode_logits(enc, labels), labels)`` at
        every position up to the last at which a row of ``labels`` holds a
        token (``decoding.common.caption_depth``; values and gradients
        pinned by test). The positions from there on are run neither forward
        nor backward (:func:`_bounded_logps`): T stays the static shape and
        the depth is data, so one compiled program serves every depth, and
        captions that fill all T positions run all of them. A caller masks
        by the tokens (a PAD position carries no loss), so the 0.0 is what
        those positions came to after the mask; rl/scst.py's update is the
        caller. Neither pass hands anyone a ``[B, T, V]`` logits stack, and
        the word embedding is outside both: all ``B x T`` input rows are
        looked up before the forward loop and their cotangents summed into
        the table after the backward loop, once a call."""
        self._lstm_only("teacher_force_logps")
        from cst_captioning_tpu.decoding.common import caption_depth

        logps = _bounded_logps(
            DecoderCell(self.cfg, parent=None), self.cell.variables["params"],
            enc.carry, (enc.memory, enc.memory_proj, enc.memory_mask),
            shift_right(labels).T, labels.T, caption_depth(labels),
        )
        return logps.T

    def __call__(
        self,
        feats: dict[str, jnp.ndarray],
        masks: dict[str, jnp.ndarray],
        labels: jnp.ndarray,
        train: bool = False,
    ) -> jnp.ndarray:
        """-> logits [B, T, V] (f32); logits[:, t] predicts labels[:, t]."""
        if self.cfg.decoder != "lstm":
            return self.decoder(feats, masks, labels)
        return self.decode_logits(self.encode(feats, masks), labels, train)
