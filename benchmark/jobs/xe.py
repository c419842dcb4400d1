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

import time

import numpy as np

from benchmark import reference, training

# the program's bf16 teacher-forced loss against the f32 reference loss on
# the same 64 rows and weights: 2e-5 apart on the chip at a 3.48-nat mean
# (PERF.md, Findings, PR 22; rounding errors average out over 600 tokens);
# another batch or other weights are off by tenths.
LOSS_ABS_TOL = 0.005
# "the loss falls over the window": mean of the last quarter of the steps
# against the first quarter's. The policy is warm-started and the window is
# a few tens of steps, so the fall is small; batch noise gets this slack
LOSS_FALL_SLACK = 0.02
CHECK_ROWS = 64


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
    checks = _checks_before(ctx, cfg, ds, trainer)

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
    failed = checks.pop("failed") + [k for k, ok in (
        ("finite", finite), ("loss_falls", last < first + LOSS_FALL_SLACK),
    ) if not ok]
    checks.update(finite=finite, loss_first=first, loss_last=last)
    out = training.window_result(clock, timer, ctx.chips, ctx.log)
    ctx.log(f"xe: {out['attempted']} steps; checks {checks}; failed {failed}")
    out.update({
        "correct": not failed,
        "failed": len(failed),
        "checks": checks,
        "caption_len_mean": checks["label_len_mean"],
        "cost_shape": {"kind": "xe", "B": B},
        "modules": {"xe": r"step"},
        "background_spans": ("prefetch.stage",),
        "step_spans": ("xe.step",),
    })
    return out


def _checks_before(ctx, cfg, ds, trainer) -> dict:
    """Outside the window: the program's teacher-forced loss on 64 fixed rows
    (dropout off) against the plain f32 reference's."""
    import jax
    import jax.numpy as jnp

    from cst_captioning_tpu.data.batcher import Batcher

    t0 = time.perf_counter()
    model = trainer.model
    b = next(iter(Batcher(ds, batch_size=CHECK_ROWS, max_len=cfg.model.max_len,
                          mode="caption", seq_per_vid=1).epoch(shuffle=False)))
    params = jax.device_put(jax.device_get(trainer.state.params),
                            jax.devices()[0])

    def program_loss(p, f, m, labels, mask):
        logp = jax.nn.log_softmax(model.apply(p, f, m, labels), axis=-1)
        tok = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
        return -(tok * mask).sum() / mask.sum()

    names = [n for n, _ in cfg.model.modalities]

    def reference_loss(p, f, m, labels, mask):
        tok = reference.token_logprobs(p, cfg.model.encoder, names, f, m, labels)
        return -(tok * mask).sum() / mask.sum()

    got = float(jax.jit(program_loss)(params, b.feats, b.feat_masks, b.labels,
                                      b.mask))
    want = float(jax.jit(reference_loss)(params, b.feats, b.feat_masks,
                                         b.labels, b.mask))
    failed = [] if abs(got - want) <= LOSS_ABS_TOL else ["loss_vs_reference"]
    return {"program_loss": got, "reference_loss": want,
            "label_len_mean": float(b.mask.sum(1).mean() - 1.0),
            "failed": failed, "checks_s": time.perf_counter() - t0}
