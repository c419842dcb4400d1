"""Job ``cst``: consensus-reward SCST through the Trainer's RL phase
(``Trainer.train_rl``: ``Batcher`` -> ``prefetch_to_device`` -> the pipelined
``SCSTTrainer.train_epoch``, native scorer), from the configuration's
warm-started policy. One chip, or the ``data`` axis over all the cell's chips.

Seam to the program: ``Trainer`` and its public attributes (``state``,
``ckpt``, ``log``, ``epoch``), ``SCSTTrainer.train_epoch``: its ``on_step``
callback (the job chains its own behind the Trainer's), its ``batches``
argument (handed on with every ``next()`` timed) and the trainer's ``decode``
and ``update`` callables (``decode`` wrapped to stamp when the rollouts are
ready and to keep hold of the newest samples; ``update`` tapped for the run's first steps, in set-up, to keep what
the reference will follow: ``following.py``);
``train/state.py``'s ``device_key`` / ``device_fold_in``; SIGTERM to stop.

Nothing here names an architecture: the plain reference, every tolerance and
the clips each check reads are the configuration's (``reference``, ``checks``
in its file), and ``correct`` comes from the programs the window runs: the
very ``decode`` and ``update`` objects ``Trainer.train_rl`` built, at the
cell's batch, rollouts and chunks, on one chip or on the mesh.
"""

from __future__ import annotations

import json
import threading
import time

import numpy as np

from benchmark import costs, following, training


def run(ctx) -> dict:
    import jax

    from cst_captioning_tpu.rl import scst as scst_mod

    traffic = ctx.workload["params"]
    cfg, ds, trainer = training.open_trainer(ctx)
    if (trainer.mesh is not None) != (ctx.chips > 1):
        raise SystemExit("the cell's chips and the trainer's mesh disagree")
    keys = _EpochKeys(cfg, trainer, traffic.get("epoch_keys_warmed", 0), ctx.log)
    compared = training.Compared()
    checks = _checks_before(ctx, cfg, ds, trainer, traffic, compared)
    first = _FirstSteps(ctx.config, cfg, traffic)
    probe_before = _probe(trainer)
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
        keys.wait()
        if not hasattr(self, "bench_decode"):
            self.bench_decode = self.decode

            def decode(*args):
                d = self.bench_decode(*args)
                clock.mark(d[1], "decode_ready")
                first.last = d[1]       # read once the window has closed
                return d

            self.decode = decode
            self.update = following.Tap(self.update, first.update)

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
    compared.holds("finite_loss_reward_gradnorm", finite)
    compared.holds("params_moved", moved > 0.0)
    compared.holds("trainer_scorer_native", native)
    checks.update(finite=finite, params_moved=moved, trainer_scorer_native=native,
                  reward_mean=float(np.mean(rewards)))
    first.read_program()
    out = training.window_result(clock, timer, ctx.chips, ctx.log)
    ctx.log(f"cst: {out['attempted']} steps; checks {checks}")
    out.update({
        "compared": compared,
        # run once the window has closed, the peak has been read and the
        # program's state is freed: the reference follows the first steps
        "verify": lambda: first.verify(compared, ctx.log),
        "followed": first,      # a control reads it (tests/read_limits.py)
        "checks": checks,
        "caption_len_mean": checks["sampled_len_mean"],
        # the work the sampled captions need: from the host copies of the
        # followed steps' samples, the window's own decode's (set-up), so
        # that nothing is read back inside the window
        "cost_shape": {"kind": "cst", "B": cfg.data.batch_size,
                       "K": cfg.rl.num_rollouts,
                       "chunks": cfg.rl.update_chunks,
                       "profile": first.profile(cfg.rl.update_chunks, ctx.log)},
        "main_thread": threading.current_thread().name,
        "modules": {"decode": r"decode", "update": r"update"},
        "background_spans": ("prefetch.stage",),
        "step_spans": ("rl.decode", "rl.reward", "rl.update"),
    })
    return out


class _FirstSteps:
    """The run's first updates, as the window's own ``update`` took them in
    set-up: host copies of each one's arguments and of the parameters before
    the first, the program's loss, and off its state Adam's first moment
    after one step (to the host: the one wait, for the first update to end)
    and the norms of the parameters' change after the last
    (``following.leaf_norms``: a few scalars, dispatched and not waited for).
    Nothing here runs the reference: ``verify`` does, after the window."""

    def __init__(self, config: dict, cfg, traffic: dict):
        self.config = config
        self.n = int(training.check_value(config, "follow_steps"))
        self.optimizer = training.check_value(config, "follow_optimizer")
        stated = {"name": cfg.train.optimizer, "lr": cfg.rl.lr,
                  "grad_clip": cfg.train.grad_clip}
        if any(self.optimizer[k] != v for k, v in stated.items()):
            raise SystemExit(f"checks.follow_optimizer {self.optimizer} is not "
                             f"the optimizer the program runs: {stated}")
        if traffic["warmup_steps"] < self.n:
            raise SystemExit("the followed steps must end before the window "
                             f"opens: warmup_steps < follow_steps = {self.n}")
        self.vocab = cfg.model.vocab_size
        self.params0 = None
        self.steps: list[dict] = []
        self.metrics: list[dict] = []
        self.moment = self.change = None
        self.last = None    # the newest decode's samples, still on the device
        self.program: dict = {}
        self.copy_s = 0.0

    def update(self, fn, state, feats, masks, samples, advantage, valid):
        import jax

        i = len(self.steps)
        if i >= self.n:
            return fn(state, feats, masks, samples, advantage, valid)
        t0 = time.perf_counter()
        if i == 0:
            self.params0 = jax.device_get(state.params)
        self.steps.append(jax.device_get({
            "feats": feats, "masks": masks, "samples": samples,
            "advantage": advantage, "valid": valid}))
        self.copy_s += time.perf_counter() - t0
        state, metrics = fn(state, feats, masks, samples, advantage, valid)
        self.metrics.append(metrics)
        if i == 0:
            t0 = time.perf_counter()
            self.moment = jax.device_get(following.adam_moment(state.opt_state))
            self.copy_s += time.perf_counter() - t0
        if i == self.n - 1:
            self.change = following.leaf_norms(state.params, self.params0)
        return state, metrics

    def read_program(self) -> None:
        """Bring the program's few scalars to the host (the window is closed)."""
        import jax

        if len(self.steps) < self.n:
            raise SystemExit(f"only {len(self.steps)} of the {self.n} steps "
                             "the reference follows were taken")
        b1 = float(self.optimizer["b1"])
        grad = jax.tree.map(lambda m: m / (1.0 - b1), self.moment)
        self.program = {
            "loss": [float(x) for x in jax.device_get(
                [m["rl_loss"] for m in self.metrics])],
            "grad_norm": [float(x) for x in jax.device_get(
                [m["grad_norm"] for m in self.metrics])],
            "grad": grad,
            "grad_leaf": following.host(following.leaf_norms(grad)),
            "change_leaf": following.host(self.change),
        }
        self.metrics, self.moment, self.change = [], None, None

    def profile(self, chunks: int, log) -> dict:
        """``costs.caption_profile`` of the followed steps' samples."""
        tokens = np.stack([s["samples"] for s in self.steps])   # [n, K, B, T]
        p = costs.caption_profile(tokens, chunks)
        _, K, B, T = tokens.shape
        log(f"profile of the {len(tokens)} followed steps' samples ({K} x {B} "
            f"lanes of {T} steps): {sum(p['lanes']) / (K * B):.3f} tokens a "
            f"lane with EOS, a batch's longest caption "
            f"{sum(p['steps']):.2f} steps; lanes alive a step "
            f"{[round(x) for x in p['lanes']]}; clips with one "
            f"{[round(x) for x in p['clips']]}")
        if self.last is not None:
            # what the count above does not see: the policy moves between the
            # followed steps (set-up) and the window's end, some tens of
            # updates later. Said beside it, every run; it changes no count
            import jax

            w = costs.caption_profile(np.asarray(jax.device_get(self.last))[None],
                                      chunks)
            self.last = None
            log(f"the window's last decode, for the drift since: "
                f"{sum(w['lanes']) / (K * B):.3f} tokens a lane with EOS, "
                f"longest caption {sum(w['steps']):.0f} steps")
        return p

    def control(self, precision: str, log) -> "training.Compared":
        """The control: the configuration's reference with every matrix
        product's operands rounded to ``precision`` (the nearest below the one
        the configuration states), put in the program's place on the same
        steps and held to the float32 reference by the same comparison; and
        the log-probability check read the same way on the first step's
        clips. It has to come out as not correct."""
        import jax

        config, held = self.config, training.Compared()
        low = following.follow(
            training.config_module(config, "reference", "token_logprobs"),
            config["model"], self.optimizer, self.params0, self.steps,
            rows=int(training.check_value(config, "follow_rows")),
            precision=precision, log=log)
        self.verify(held, log, program=low)
        n = int(training.check_value(config, "logprob_check_clips"))
        step = self.steps[0]
        cut = lambda x: x[:n]  # noqa: E731
        tokens = step["samples"][0, :n]
        f32, coarse = (training.reference_logprobs(
            config, self.params0, jax.tree.map(cut, step["feats"]),
            jax.tree.map(cut, step["masks"]), tokens, rows=n,
            forbid_special=True, precision=p) for p in ("float32", precision))
        held.at_most("decode_logprob_mean_abs_diff",
                     np.abs(f32 - coarse)[tokens != 0].mean(),
                     training.check_value(config, "logprob_mean_abs_tol"))
        return held

    def verify(self, compared, log, program=None) -> dict:
        """The reference follows the steps; every number goes into
        ``compared`` beside its limit. ``program`` stands in for the
        program's readings in a control."""
        config = self.config
        tokens = np.stack([s["samples"] for s in self.steps])
        compared.at_least("sampled_token_id_min", tokens.min(), 0)
        compared.at_most("sampled_token_id_max", tokens.max(), self.vocab - 1)
        ref = following.follow(
            training.config_module(config, "reference", "token_logprobs"),
            config["model"], self.optimizer, self.params0, self.steps,
            rows=int(training.check_value(config, "follow_rows")), log=log)
        program = self.program if program is None else program
        limits = {k: training.check_value(config, k) for k in (
            "rl_loss_abs_tol", "grad_leaf_gap_tol", "grad_rel_diff_tol",
            "change_leaf_gap_tol")}
        said = following.compare(compared, program, ref, limits)
        K, B, _ = self.steps[0]["samples"].shape
        log(f"first {self.n} updates of the window's own program, {B} clips x "
            f"{K} rollouts = {K * B} rows each (host copies took "
            f"{self.copy_s:.2f}s of set-up): rl_loss of the program "
            f"{said['loss_program']}, of the reference {said['loss_reference']}, "
            f"differences {[abs(a - b) for a, b in zip(said['loss_program'], said['loss_reference'])]}"
            f" (tolerance {limits['rl_loss_abs_tol']}); grad_norm of the program "
            f"{program['grad_norm']}, of the reference {ref['grad_norm']}; worst "
            f"leaves: first gradient {said['first_grad_worst_leaf']}, change "
            f"{said['param_change_worst_leaf']}")
        return ref


class _EpochKeys(threading.Thread):
    """``Trainer.train_rl`` derives every epoch's sampling key with a program
    that has the epoch number compiled in (``device_fold_in``): one compile,
    or compile-cache load, an epoch, 0.45 s on one chip and 0.7 s on four even
    from the cache. They are run in set-up for every epoch the window can
    reach, so that nothing compiles inside the window: on a thread of their
    own, beside the checks and the reward's set-up, and waited for
    (``wait``) before the first epoch begins. (Eight threads at once took as
    long as one: the loads queue behind each other. My chip runs, PR 27.)"""

    def __init__(self, cfg, trainer, epochs: int, log):
        super().__init__(name="bench-epoch-keys", daemon=True)
        self.seed = cfg.train.seed + 1
        self.epochs = range(trainer.epoch, trainer.epoch + epochs)
        self.log, self.error, self.took, self.waited = log, None, 0.0, False
        self.start()

    def run(self) -> None:
        from cst_captioning_tpu.train.state import device_fold_in, device_key

        t0 = time.perf_counter()
        try:
            base = device_key(self.seed)
            for epoch in self.epochs:
                device_fold_in(base, epoch)
        except Exception as e:      # raised in the main thread by wait()
            self.error = e
        self.took = time.perf_counter() - t0

    def wait(self) -> None:
        """Called where the first epoch begins; later calls return at once."""
        if self.waited:
            return
        self.waited, t0 = True, time.perf_counter()
        self.join()
        if self.error is not None:
            raise self.error
        self.log(f"set-up: {len(self.epochs)} epoch keys warmed in "
                 f"{self.took:.1f}s beside the set-up; the first epoch waited "
                 f"{time.perf_counter() - t0:.1f}s for them")


def _probe(trainer) -> np.ndarray:
    """The largest parameter leaf on the host: did training move it. (A
    leaf may rightly stand still: attention over one slot has no gradient.)"""
    import jax

    leaf = max(jax.tree.leaves(trainer.state.params), key=lambda x: x.size)
    return np.asarray(jax.device_get(leaf), np.float32)


def _first_batch(ds, cfg, n: int):
    from cst_captioning_tpu.data.batcher import Batcher

    return next(iter(Batcher(ds, batch_size=n, max_len=cfg.model.max_len,
                             mode="video").epoch(shuffle=False)))


def _checks_before(ctx, cfg, ds, trainer, traffic, compared) -> dict:
    """Outside the window, on a seeded sample: the decode's log-probabilities
    against the configuration's reference, the native scorer against the
    Python one, the policy's caption lengths, and on several chips the sharded
    update against the one-device update. Tolerances and sample sizes are the
    configuration's ``checks``."""
    import jax

    from cst_captioning_tpu.decoding.greedy import greedy_decode
    from cst_captioning_tpu.decoding.sample import sample_decode
    from cst_captioning_tpu.rl import RewardComputer

    t0 = time.perf_counter()
    config = ctx.config
    model, K = trainer.model, cfg.rl.num_rollouts
    clips = int(training.check_value(config, "logprob_check_clips"))
    b = _first_batch(ds, cfg, clips)
    params = jax.device_put(jax.device_get(trainer.state.params),
                            jax.devices()[0])
    rng = jax.random.key(ctx.seed)
    samples, logps = jax.jit(lambda p, f, m, r: sample_decode(
        model, p, f, m, r, num_rollouts=K, temperature=cfg.rl.temperature,
    ))(params, b.feats, b.feat_masks, rng)
    greedy, _ = jax.jit(lambda p, f, m: greedy_decode(model, p, f, m))(
        params, b.feats, b.feat_masks)
    samples, logps, greedy = jax.device_get((samples, logps, greedy))
    out = training.check_policy_lengths(samples, greedy, traffic["policy_check"],
                                        compared)

    ref = training.reference_logprobs(
        config, params, b.feats, b.feat_masks, samples[0], rows=clips,
        forbid_special=True)
    real = samples[0] != 0
    gap = float(np.abs(ref - logps[0])[real].mean())
    out["logprob_mean_abs_diff"] = gap
    out["logprob_max_abs_diff"] = float(np.abs(ref - logps[0])[real].max())
    compared.at_most("decode_logprob_mean_abs_diff", gap,
                     training.check_value(config, "logprob_mean_abs_tol"))
    compared.at_least("decode_token_id_min", samples.min(), 0)
    compared.at_most("decode_token_id_max", samples.max(),
                     cfg.model.vocab_size - 1)

    # native scorer against the Python scorer on the same rows
    # (document frequencies from these videos' references, in both)
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
    compared.holds("check_scorer_native", nat.scorer == "native")
    compared.at_most("reward_native_vs_python_max_abs",
                     out["reward_native_vs_python_max_abs"],
                     training.check_value(config, "reward_abs_tol"))

    if trainer.mesh is not None:
        out.update(_mesh_check(ctx, cfg, ds, trainer, nat, compared))
    out["checks_s"] = time.perf_counter() - t0
    return out


def _mesh_check(ctx, cfg, ds, trainer, reward, compared) -> dict:
    """First-step loss and grad-norm of the sharded update against the
    one-device update on the same clips, rollouts and advantages (the
    check that would have caught PR 21's n_devices-fold gradient)."""
    import jax
    from jax.sharding import PartitionSpec as P

    from cst_captioning_tpu.rl import SCSTTrainer
    from cst_captioning_tpu.train import multihost
    from cst_captioning_tpu.train.mesh import shard_batch

    mesh, T = trainer.mesh, cfg.model.max_len
    b = _first_batch(ds, cfg, int(training.check_value(ctx.config,
                                                       "mesh_check_clips")))
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
    tol = training.check_value(ctx.config, "mesh_rel_tol")
    rel = lambda a, c: abs(a - c) / max(abs(c), 1e-3)  # noqa: E731
    compared.at_most("mesh_vs_one_rl_loss_rel_diff", rel(l4, l1), tol)
    compared.at_most("mesh_vs_one_grad_norm_rel_diff", rel(g4, g1), tol)
    return {"mesh_vs_one_rl_loss": [l4, l1], "mesh_vs_one_grad_norm": [g4, g1]}
