"""Job ``cst``: consensus-reward SCST through the Trainer's RL phase
(``Trainer.train_rl``: ``Batcher`` -> ``prefetch_to_device`` -> the pipelined
``SCSTTrainer.train_epoch``, native scorer), from the configuration's
warm-started policy. One chip, or the ``data`` axis over all the cell's chips.

Seam to the program: ``Trainer`` and its public attributes (``state``,
``ckpt``, ``log``, ``epoch``), ``SCSTTrainer.train_epoch``: its ``on_step``
callback (the job chains its own behind the Trainer's), its ``batches``
argument (handed on with every ``next()`` timed) and the trainer's ``decode``
callable (wrapped to stamp when the rollouts are ready);
``train/state.py``'s ``device_key`` / ``device_fold_in``; SIGTERM to stop.
"""

from __future__ import annotations

import json
import time

import numpy as np

from benchmark import reference, training

# bf16 compute (as the configuration states) against the f32 reference: the
# per-token log-probability of a sampled token differs by bf16 rounding of
# the 512-wide dot products in front of a 9000-way softmax: 0.0016 mean on
# the chip (PERF.md, Findings, PR 22). f32 compute agrees to 2.5e-7; a wrong
# program (other rows, other weights) is off by whole nats; the bar is six
# times what bf16 shows, so a coarser type than the configuration's fails.
LOGPROB_MEAN_ABS_TOL = 0.01
# four chips against one on the same 256-clip batch: the same sums in
# another order, in bf16 (chip_smoke.py's bar)
MESH_REL_TOL = 2e-2
CHECK_CLIPS = 64
MESH_CHECK_CLIPS = 256


def run(ctx) -> dict:
    import jax

    from cst_captioning_tpu.rl import scst as scst_mod

    traffic = ctx.workload["params"]
    cfg, ds, trainer = training.open_trainer(ctx)
    if (trainer.mesh is not None) != (ctx.chips > 1):
        raise SystemExit("the cell's chips and the trainer's mesh disagree")
    checks = _checks_before(ctx, cfg, ds, trainer, traffic)
    probe_before = _probe(trainer)
    _warm_epoch_keys(cfg, trainer, traffic.get("epoch_keys_warmed", 0))
    ctx.log(f"set-up: checks done in {checks['checks_s']:.1f}s; the loop "
            "starts (reward init, then the warm-up epoch)")

    seen: list[dict] = []
    timer = training.LoopTimer()
    period = -(-len(ds) // cfg.data.batch_size)     # steps an epoch
    clock = training.StepClock(traffic["warmup_steps"], ctx.seconds,
                               on_open=ctx.window_opened,
                               on_close=ctx.window_closed,
                               on_step=ctx.step_listener(period),
                               period=period, chips=ctx.chips)
    original = scst_mod.SCSTTrainer.train_epoch

    def train_epoch(self, state, batches, rng, on_step=None, **kw):
        timer.entered()
        if not hasattr(self, "bench_decode"):
            self.bench_decode = self.decode

            def decode(*args):
                d = self.bench_decode(*args)
                clock.mark(d[1], "decode_ready")
                return d

            self.decode = decode

        def both(m):
            if on_step is not None:
                on_step(m)
            seen.append(m)
            clock.submit(m["rl_loss"], m["valid_rows"])

        try:
            return original(self, state, timer.batches(batches), rng,
                            on_step=both, **kw)
        finally:
            timer.left()

    scst_mod.SCSTTrainer.train_epoch = train_epoch
    try:
        training.train_until_closed(ctx, trainer, ds, clock, "train_rl")
    finally:
        scst_mod.SCSTTrainer.train_epoch = original

    # finite loss / reward / grad-norm at every step; parameters moved; the
    # Trainer's own scorer (named in its event log) is the native one
    dev = jax.device_get([(m["rl_loss"], m["grad_norm"]) for m in seen])
    rewards = [m["reward_mean"] for m in seen]
    finite = bool(np.all(np.isfinite(np.asarray(dev, np.float64)))
                  and np.all(np.isfinite(rewards)))
    moved = float(np.max(np.abs(_probe(trainer) - probe_before)))
    with open(training.events_path(ctx)) as f:
        scorers = [e["scorer"] for e in map(json.loads, f)
                   if e["event"] == "reward_scorer"]
    native = scorers[-1:] == ["native"]
    failed = checks.pop("failed") + [k for k, ok in (
        ("finite", finite), ("params_moved", moved > 0.0),
        ("trainer_scorer_native", native),
    ) if not ok]
    checks.update(finite=finite, params_moved=moved, trainer_scorer_native=native,
                  reward_mean=float(np.mean(rewards)))
    out = training.window_result(clock, timer, ctx.chips, ctx.log)
    ctx.log(f"cst: {out['attempted']} steps; checks {checks}; failed {failed}")
    out.update({
        "correct": not failed,
        "failed": len(failed),
        "checks": checks,
        "caption_len_mean": checks["sampled_len_mean"],
        "cost_shape": {"kind": "cst", "B": cfg.data.batch_size,
                       "K": cfg.rl.num_rollouts,
                       "chunks": cfg.rl.update_chunks},
        "modules": {"decode": r"decode", "update": r"update"},
        "background_spans": ("prefetch.stage",),
        "step_spans": ("rl.decode", "rl.reward", "rl.update"),
    })
    return out


def _warm_epoch_keys(cfg, trainer, epochs: int) -> None:
    """``Trainer.train_rl`` derives every epoch's sampling key with a program
    that has the epoch number compiled in (``device_fold_in``): one compile,
    or compile-cache load, an epoch. Run it in set-up for every epoch the
    window can reach, so that nothing compiles inside the window."""
    from cst_captioning_tpu.train.state import device_fold_in, device_key

    base = device_key(cfg.train.seed + 1)
    for epoch in range(trainer.epoch, trainer.epoch + epochs):
        device_fold_in(base, epoch)


def _probe(trainer) -> np.ndarray:
    """One parameter leaf on the host: did training move it."""
    import jax

    return np.asarray(jax.device_get(jax.tree.leaves(trainer.state.params)[0]),
                      np.float32)


def _first_batch(ds, cfg, n: int):
    from cst_captioning_tpu.data.batcher import Batcher

    return next(iter(Batcher(ds, batch_size=n, max_len=cfg.model.max_len,
                             mode="video").epoch(shuffle=False)))


def _checks_before(ctx, cfg, ds, trainer, traffic) -> dict:
    """Outside the window, on a seeded sample: the decode's log-probabilities
    against the plain reference, the native scorer against the Python one,
    the policy's caption lengths, and on several chips the sharded update
    against the one-device update."""
    import jax

    from cst_captioning_tpu.decoding.greedy import greedy_decode
    from cst_captioning_tpu.decoding.sample import sample_decode
    from cst_captioning_tpu.rl import RewardComputer

    t0 = time.perf_counter()
    failed: list[str] = []
    model, K = trainer.model, cfg.rl.num_rollouts
    b = _first_batch(ds, cfg, CHECK_CLIPS)
    params = jax.device_put(jax.device_get(trainer.state.params),
                            jax.devices()[0])
    rng = jax.random.key(ctx.seed)
    samples, logps = jax.jit(lambda p, f, m, r: sample_decode(
        model, p, f, m, r, num_rollouts=K, temperature=cfg.rl.temperature,
    ))(params, b.feats, b.feat_masks, rng)
    greedy, _ = jax.jit(lambda p, f, m: greedy_decode(model, p, f, m))(
        params, b.feats, b.feat_masks)
    samples, logps, greedy = jax.device_get((samples, logps, greedy))
    out = training.check_policy_lengths(samples, greedy, traffic["policy_check"])

    names = [n for n, _ in cfg.model.modalities]
    ref = np.asarray(jax.jit(lambda p, f, m, t: reference.token_logprobs(
        p, cfg.model.encoder, names, f, m, t, forbid_special=True,
    ))(params, b.feats, b.feat_masks, samples[0]))
    real = samples[0] != 0
    gap = float(np.abs(ref - logps[0])[real].mean())
    out["logprob_mean_abs_diff"] = gap
    out["logprob_max_abs_diff"] = float(np.abs(ref - logps[0])[real].max())
    if not gap <= LOGPROB_MEAN_ABS_TOL:
        failed.append("decode_logprobs_vs_reference")
    V = cfg.model.vocab_size
    if samples.min() < 0 or samples.max() >= V:
        failed.append("token_ids_in_range")

    # native scorer against the Python scorer on the same rows
    # (document frequencies from these 64 videos' references, in both)
    pool = {v: ds.gts_pool()[v] for v in b.video_ids}
    kw = dict(cider_weight=cfg.rl.reward_cider_weight,
              bleu_weight=cfg.rl.reward_bleu4_weight,
              bleu_scale=cfg.rl.reward_bleu4_scale)
    nat = RewardComputer(ds.vocab, pool, **kw)
    py = RewardComputer(ds.vocab, pool, use_native=False, **kw)
    r_nat = nat(b.video_ids, samples[0])
    r_py = py(b.video_ids, samples[0])
    out["scorer"] = nat.scorer
    out["reward_native_vs_python_max_abs"] = float(np.abs(r_nat - r_py).max())
    if nat.scorer != "native" or not np.allclose(r_nat, r_py, atol=1e-4):
        failed.append("native_scorer_equals_python")

    if trainer.mesh is not None:
        out.update(_mesh_check(ctx, cfg, ds, trainer, nat, failed))
    out["failed"] = failed
    out["checks_s"] = time.perf_counter() - t0
    return out


def _mesh_check(ctx, cfg, ds, trainer, reward, failed) -> dict:
    """First-step loss and grad-norm of the sharded update against the
    one-device update on the same 256 clips, rollouts and advantages (the
    check that would have caught PR 21's n_devices-fold gradient)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from cst_captioning_tpu.rl import SCSTTrainer
    from cst_captioning_tpu.train import multihost
    from cst_captioning_tpu.train.mesh import shard_batch

    mesh, T = trainer.mesh, cfg.model.max_len
    b = _first_batch(ds, cfg, MESH_CHECK_CLIPS)
    on_mesh = SCSTTrainer(trainer.model, reward, cfg.rl, mesh=mesh, max_len=T,
                          guard=True)
    on_one = SCSTTrainer(trainer.model, reward, cfg.rl, mesh=None, max_len=T,
                         guard=True)
    feats4, masks4 = shard_batch(mesh, (b.feats, b.feat_masks))
    _, samples = on_mesh.decode(trainer.state.params, feats4, masks4,
                                jax.random.key(ctx.seed + 1))
    samples_np = np.asarray(jax.device_get(samples))
    K, B, _ = samples_np.shape
    adv = np.random.default_rng(ctx.seed).normal(size=(K, B)).astype(np.float32)
    valid = np.ones((B,), np.float32)
    _, u4 = on_mesh.update(
        trainer.state, feats4, masks4, samples,
        multihost.from_host_local(adv, mesh, P(None, "data")),
        multihost.from_host_local(valid, mesh, P("data")),
    )
    one_state = jax.device_put(jax.device_get(trainer.state), jax.devices()[0])
    _, u1 = on_one.update(one_state, b.feats, b.feat_masks, samples_np, adv,
                          valid)
    l4, l1 = float(u4["rl_loss"]), float(u1["rl_loss"])
    g4, g1 = float(u4["grad_norm"]), float(u1["grad_norm"])
    close = lambda a, c: abs(a - c) <= MESH_REL_TOL * max(abs(c), 1e-3)  # noqa: E731
    if not (close(l4, l1) and close(g4, g1)):
        failed.append("mesh_update_equals_one_device")
    return {"mesh_vs_one_rl_loss": [l4, l1], "mesh_vs_one_grad_norm": [g4, g1]}
