"""Job ``xe``: teacher-forced cross-entropy through the Trainer's XE phase
(``Trainer.train_xe``: ``Batcher`` -> ``prefetch_to_device`` -> the donated,
guarded XE step), from the configuration's warm-started policy.

Seam to the program: ``Trainer`` and its public attributes (``state``,
``ckpt``, ``log``, ``xe_step`` — the job wraps the step callable to see each
dispatch), ``Trainer._device_batches`` (the epoch's batch iterator, handed on
with every ``next()`` timed), SIGTERM to stop. "Clips" are caption rows here: one row is one
clip's features teacher-forced against one reference.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from benchmark import training

# the reference, the tolerances (``loss_abs_tol``, ``loss_fall_slack``) and the
# rows the check reads (``xe_check_rows``) are the configuration's: its file's
# ``reference`` and ``checks``


class _TimedStep:
    """``Trainer.xe_step`` with every dispatch reported to the step clock."""

    def __init__(self, step, clock, clips_of_step, losses):
        self._step, self._clock = step, clock
        self._clips_of_step, self._losses = clips_of_step, losses
        self._n = 0

    def __call__(self, state, *batch):
        state, m = self._step(state, *batch)
        self._clock.submit(m["loss"], self._clips_of_step(self._n))
        self._losses.append(m["loss"])
        self._n += 1
        return state, m

    def __getattr__(self, name):
        return getattr(self._step, name)


def run(ctx) -> dict:
    import jax

    cfg, ds, trainer = training.open_trainer(ctx)
    compared = training.Compared()
    checks = _checks_before(ctx, cfg, ds, trainer, compared)

    # the last batch of an epoch is wrap-padded to the static batch: only
    # its valid rows count (the Batcher's documented schedule)
    B = cfg.data.batch_size
    rows = sum(min(cfg.data.seq_per_vid, len(r.caption_ids))
               for r in ds.records)
    per_epoch = -(-rows // B)

    def clips_of_step(i: int) -> int:
        return min(B, rows - (i % per_epoch) * B)

    losses: list = []
    timer = training.LoopTimer()
    clock = training.StepClock(ctx.workload["params"]["warmup_steps"],
                               ctx.seconds, on_open=ctx.window_opened,
                               on_close=ctx.window_closed,
                               on_step=ctx.step_listener(per_epoch),
                               period=per_epoch, chips=ctx.chips)
    trainer.xe_step = _TimedStep(trainer.xe_step, clock, clips_of_step, losses)
    device_batches = trainer._device_batches

    def timed_batches(*args, **kw):
        timer.entered()
        return timer.batches(device_batches(*args, **kw), then=timer.left)

    trainer._device_batches = timed_batches
    training.train_until_closed(ctx, trainer, ds, clock, "train_xe")

    vals = np.asarray(jax.device_get(losses), np.float64)
    finite = bool(np.all(np.isfinite(vals)))
    q = max(len(vals) // 4, 1)
    first, last = float(vals[:q].mean()), float(vals[-q:].mean())
    compared.holds("finite_loss", finite)
    # "the loss falls over the window": the last quarter of the steps against
    # the first quarter's; the policy is warm-started and the window is a few
    # tens of steps, so the fall is small and batch noise gets the slack
    compared.at_most("loss_last_minus_first_quarter", last - first,
                     training.check_value(ctx.config, "loss_fall_slack"))
    checks.update(finite=finite, loss_first=first, loss_last=last)
    out = training.window_result(clock, timer, ctx.chips, ctx.log)
    ctx.log(f"xe: {out['attempted']} steps; checks {checks}")
    out.update({
        "compared": compared,
        "checks": checks,
        "caption_len_mean": checks["label_len_mean"],
        "cost_shape": {"kind": "xe", "B": B,
                       "profile": _profile(ds, cfg, B, per_epoch)},
        "main_thread": threading.current_thread().name,
        "modules": {"xe": r"step"},
        "background_spans": ("prefetch.stage",),
        "step_spans": ("xe.step",),
    })
    return out


def _profile(ds, cfg, B: int, per_epoch: int) -> dict:
    """The work a batch of reference rows needs, in ``costs.caption_profile``'s
    keys, from the corpus' own lengths (each video's first ``seq_per_vid``
    references; which ones an epoch draws is the seed's, their lengths are
    the corpus'): one lane a row, EOS included. ``p_t`` is the share of rows
    that hold a token at ``t``; a batch of ``B`` rows drawn from them holds
    one with probability ``1 - (1 - p_t)^B``, and the epoch's mean batch
    (the padded last one by its valid rows) has ``rows / per_epoch`` rows."""
    T = cfg.model.max_len
    lens = np.array([min(len(c) + 1, T) for r in ds.records
                     for c in r.caption_ids[:cfg.data.seq_per_vid]])
    p = (np.arange(T)[None, :] < lens[:, None]).mean(0)
    rows = [float(x) for x in p * len(lens) / per_epoch]
    steps = [float(x) for x in 1.0 - (1.0 - p) ** B]
    return {"lanes": rows, "clips": rows, "chunk_clips": rows,
            "steps": steps, "chunk_steps": steps}


def _checks_before(ctx, cfg, ds, trainer, compared) -> dict:
    """Outside the window: the program's teacher-forced loss on fixed rows
    (dropout off) against the configuration's plain f32 reference's."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.data.batcher import Batcher

    t0 = time.perf_counter()
    model, config = trainer.model, ctx.config
    rows = int(training.check_value(config, "xe_check_rows"))
    b = next(iter(Batcher(ds, batch_size=rows, max_len=cfg.model.max_len,
                          mode="caption", seq_per_vid=1).epoch(shuffle=False)))
    params = jax.device_put(jax.device_get(trainer.state.params),
                            jax.devices()[0])

    def program_loss(p, f, m, labels, mask):
        logp = jax.nn.log_softmax(model.apply(p, f, m, labels), axis=-1)
        tok = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -(tok * mask).sum() / mask.sum()

    got = float(jax.jit(program_loss)(params, b.feats, b.feat_masks, b.labels,
                                      b.mask))
    tok = training.reference_logprobs(config, params, b.feats, b.feat_masks,
                                      b.labels, rows=rows)
    mask = np.asarray(b.mask, np.float64)
    want = float(-(tok * mask).sum() / mask.sum())
    compared.at_most("xe_loss_abs_diff", abs(got - want),
                     training.check_value(config, "loss_abs_tol"))
    return {"program_loss": got, "reference_loss": want,
            "label_len_mean": float(b.mask.sum(1).mean() - 1.0),
            "checks_s": time.perf_counter() - t0}
