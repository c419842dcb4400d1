"""Job ``eval``: beam-search evaluation of the configuration's warm-started
policy through the program's ``Evaluator``, built as ``cli/eval.py`` builds it
(the preset's ``EvalConfig``: its beam, its metric table, its two-stage
decode/score pipeline; the workload's batch size; a mesh over the cell's
chips), pass after pass over the split the workload's ``params`` name. One
chip, or the ``data`` axis over all the cell's chips.

A step is one decoded batch; a pass is every batch of the split and then the
pass's scoring (``Evaluator.evaluate`` returning), and the window opens and
closes on a pass's end, as ``cst``'s does on an epoch's: ``period`` is the
batches of a pass plus that end. ``clips_per_s_per_chip`` = clips whose
captions were decoded and scored in the window / window seconds / chips.

Seam to the program: ``Evaluator`` and its attributes ``_decode`` (the
compiled beam search: tapped, so that every dispatch is a step of the clock
and the tokens it emitted are kept) and ``batcher`` (its ``epoch`` handed on
with every ``next()`` timed; an unshuffled epoch walks ``ds.records`` in
order, which is how a row of a pass is matched to its clip);
``CaptionModel`` / ``load_params`` / ``make_mesh`` / ``replicate`` as
``cli/eval.py`` uses them; ``obs.configure`` / ``obs.shutdown`` for the spans.

``correct`` (``_Emitted.verify``, after the window): of the captions the
timed decode itself emitted in the window's last whole pass, a sample of
``eval_check_clips`` clips drawn from the seed with the longest caption in
it. The configuration's reference (float32, in blocks of rows, nothing of
the program imported) runs its own plain beam search over the same clips
(``beam_search``) and walks teacher-forced along each emitted caption
(``beam_logprobs``). Held from the timed tokens, two-sided: the share of
token positions at which the emitted caption and the reference's differ
(``beam_token_mismatch_tol``: a greedy search, a wrongly ranked final
hypothesis and a lower precision all move captions off the reference's), and
the mean distance between the reference's score of the emitted caption and
the score of its own best (``beam_score_gap_tol``). One-sided beside them:
a beam of that width can only have kept tokens among the ``beam`` most
probable after their prefix, so the widest gap by which an emitted token lies
under that edge is held to ``beam_rank_gap_tol`` (whole nats for a token
altered where it is produced). Last, not from the timed program but from the
program's own step (``encode``, then ``decode_step`` on the carry, forced
along the same tokens: prefill, then the cache), the log-probabilities
against the reference's, held by ``beam_logprob_mean_abs_tol``; and the token
ids to the vocabulary.
Nothing here names an architecture.
"""

from __future__ import annotations

import signal
import threading
import time

import numpy as np

from benchmark import costs, training
from benchmark.corpus import ensure_corpus

PAD_ID, BOS_ID = 0, 1


def run(ctx) -> dict:
    import jax

    from cst_captioning_tpu import obs
    from cst_captioning_tpu.ckpt import load_params
    from cst_captioning_tpu.data.batcher import Batcher
    from cst_captioning_tpu.eval.evaluator import Evaluator
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.train.mesh import make_mesh, replicate
    from cst_captioning_tpu.train.steps import batch_arrays

    config, traffic = ctx.config, ctx.workload["params"]
    paths = ensure_corpus(ctx.cache_dir, config["corpus"])
    policy_dir = training.ensure_policy(ctx.cache_dir, config, paths, ctx.log)
    cfg = training.experiment_config(config, traffic, ctx.seed, ctx.run_dir,
                                     ctx.chips,
                                     obs_dir=ctx.obs_dir if ctx.trace else "")
    ctx.log("set-up: corpus and policy in the cache")
    if cfg.train.obs:
        obs.configure(cfg.train.obs_dir, run=f"{cfg.name}-eval")
    ds = training.open_train_split(cfg, paths, split=traffic["split"])
    model = CaptionModel(cfg.model)
    # template parameters from a throwaway init on two rows, as the CLI does
    sample = next(iter(Batcher(ds, batch_size=2, max_len=cfg.model.max_len,
                               mode="video").epoch(False)))
    feats, masks, labels, *_ = batch_arrays(sample)
    params = load_params(policy_dir, "latest",
                         model.init(jax.random.key(0), feats, masks, labels))
    host_params = jax.device_get(params)
    mesh = make_mesh(ctx.chips) if ctx.chips > 1 else None
    if mesh is not None:
        params = replicate(mesh, params)
    if cfg.eval.min_len or cfg.eval.npad_lanes or cfg.eval.beam_size < 2:
        raise SystemExit("job eval holds a plain beam search: min_len, "
                         "npad_lanes and a beam of 1 have no reference here")
    ev = Evaluator(model, ds, cfg.eval, batch_size=cfg.data.batch_size,
                   mesh=mesh)
    ctx.log(f"set-up: Evaluator built (beam {cfg.eval.beam_size}, batch "
            f"{ev.batcher.batch_size}, metrics {list(cfg.eval.metrics)}, "
            f"pipelined {cfg.eval.pipelined}), policy loaded")

    B = ev.batcher.batch_size
    batches = -(-len(ds.records) // B)
    period = batches + 1              # a pass's batches, then its end
    clock = training.StepClock(traffic["warmup_passes"] * period, ctx.seconds,
                               on_open=ctx.window_opened,
                               on_close=ctx.window_closed,
                               on_step=ctx.step_listener(period),
                               period=period, chips=ctx.chips)
    timer = training.LoopTimer()
    emitted = _Emitted(config, cfg, ds, model, host_params, ctx.seed)

    decode, epoch = ev._decode, ev.batcher.epoch

    def tapped(*args):
        tokens = decode(*args)
        # the wrap-padded rows of a pass's last batch are decoded, not counted
        clock.submit(tokens, min(B, len(ds.records) - len(emitted.now) * B))
        emitted.now.append(tokens)
        return tokens

    ev._decode = tapped
    ev.batcher.epoch = lambda *a, **kw: timer.batches(epoch(*a, **kw))
    table, passes = None, 0
    # the clock asks the program to stop with SIGTERM (a training loop's
    # preemption); this loop stops itself at a pass's end, so it takes the
    # clock's signal until the clock's thread has ended. Anybody else's
    # SIGTERM ends the process as it would have
    def taken(signum, _frame):
        if clock.t_close is None and clock.error is None:
            signal.signal(signum, handler)
            signal.raise_signal(signum)

    handler = signal.signal(signal.SIGTERM, taken)
    try:
        with ctx.annotate("eval"):
            while clock.t_close is None and clock.error is None:
                emitted.now = []
                result = ev.evaluate(params)
                # the pass's end: every caption decoded and scored. Nothing is
                # left on the device, so the clock stamps it as it reads it;
                # the mark behind it says the clock is done with it (window
                # opened or closed, the stop asked for), and is waited for
                clock.submit(np.zeros(()), 0)
                clock.mark(np.zeros(()), "pass_end")
                passes += 1
                while (len(clock.marks.get("pass_end", ())) < passes
                       and clock.error is None):
                    time.sleep(0.0002)
                if clock.t_open is not None and clock.done[-1][0] > clock.t_open:
                    emitted.keep()      # a whole pass inside the window
                    table = result["metrics"]
    finally:
        ev._decode, ev.batcher.epoch = decode, epoch
        clock.finish()
        signal.signal(signal.SIGTERM, handler)
        if table is not None:
            emitted.sample()
        ds.close()
        obs.shutdown()

    compared = training.Compared()
    finite = bool(table) and all(np.isfinite(float(v)) for v in table.values())
    compared.holds("metric_table_finite", finite)
    compared.at_least("captions_of_a_pass", len(result["captions"]),
                      len(ds.records))
    out = training.window_result(clock, timer, ctx.chips, ctx.log)
    steps = [s for s in out["steps"] if s[1] > 0]       # the decoded batches
    tokens = emitted.tokens
    ctx.log(f"eval: {len(steps)} batches in {len(out['steps']) - len(steps)} "
            f"passes; metric table of the last {table}")
    out.update({
        "attempted": len(steps), "steps": steps,
        "compared": compared,
        "verify": lambda: emitted.verify(compared, ctx.log),
        "emitted": emitted,     # a control reads it (tests)
        "checks": {"table": table},
        "caption_len_mean": float(training.caption_lengths(tokens).mean()),
        # the work the emitted captions need: every lane of a clip's beam is
        # taken to hold a token as long as the caption it emitted does
        "cost_shape": {"kind": "eval", "B": B, "beam": cfg.eval.beam_size,
                       "profile": costs.caption_profile(
                           np.repeat(tokens[:, None], cfg.eval.beam_size, 1))},
        "main_thread": threading.current_thread().name,
        # the Evaluator compiles its decode from a lambda, and the name is all
        # a trace has of a program: ``jit__lambda`` on the chip (behind a
        # wrapper's prefix on a mesh), and the one jitted lambda a pass runs
        "modules": {"eval_decode": r"^jit_\w*_lambda_?$"},
        "background_spans": (),
        "step_spans": ("data.collate", "eval.pipeline.drain"),
    })
    return out


class _Emitted:
    """The tokens the window's own decode emitted: device arrays as the tap
    saw them (``now``: the pass under way), kept pass by pass, and after the
    window the sample the reference reads."""

    def __init__(self, config: dict, cfg, ds, model, host_params, seed: int):
        self.config, self.cfg, self.ds, self.model = config, cfg, ds, model
        self.params, self.seed = host_params, seed
        self.now: list = []
        self.kept: list = []
        self.tokens = self.rows = self.feats = self.masks = None
        self._jitted: dict = {}     # the reference, compiled once a precision

    def keep(self) -> None:
        self.kept = self.now

    def sample(self) -> None:
        """Bring the kept pass to the host ([batches, B, T]; the wrap-padded
        rows of the last batch are real clips decoded again) and draw the
        clips the check reads: ``eval_check_clips`` of the split from the
        seed, the one with the longest caption among them; their features
        from the dataset, which closes after this."""
        import jax

        self.tokens = np.stack([np.asarray(t) for t in jax.device_get(self.kept)])
        self.kept = self.now = []
        flat = self.tokens.reshape(-1, self.tokens.shape[-1])[:len(self.ds.records)]
        n = min(int(training.check_value(self.config, "eval_check_clips")),
                len(flat))
        rng = np.random.default_rng(self.seed)
        longest = int(np.argmax((flat != PAD_ID).sum(1)))
        others = rng.permutation(np.delete(np.arange(len(flat)), longest))
        self.rows = np.sort(np.concatenate([[longest], others[:n - 1]]))
        read = [self.ds.features_for(self.ds.records[i].video_id)
                for i in self.rows]
        names = self.cfg.model.modality_names
        self.feats = {k: np.stack([r[k][0] for r in read]) for k in names}
        self.masks = {k: np.stack([r[k][1] for r in read]) for k in names}
        self.sampled = flat[self.rows]

    def _reference(self, entry: str, *tokens, precision="float32"):
        """``entry`` of the configuration's reference (``beam_search``, or
        ``beam_logprobs`` along ``tokens``) over the sample, in blocks of
        ``follow_rows`` rows; -> its outputs."""
        import jax

        config, ev = self.config, self.cfg.eval
        if (entry, precision) not in self._jitted:
            ref = training.config_module(config, "reference", entry)
            model = config["model"]
            if entry == "beam_search":
                fn = lambda p, f, m: ref.beam_search(  # noqa: E731
                    p, model, f, m, ev.beam_size, self.sampled.shape[-1],
                    length_penalty=ev.length_penalty, precision=precision)
            else:
                fn = lambda p, f, m, t: ref.beam_logprobs(  # noqa: E731
                    p, model, f, m, t, ev.beam_size, precision=precision)
            self._jitted[entry, precision] = jax.jit(fn)
        fn = self._jitted[entry, precision]
        rows = int(training.check_value(config, "follow_rows"))
        out = []
        for a in range(0, len(self.sampled), rows):
            cut = lambda x: x[a:a + rows]  # noqa: E731
            out.append(jax.device_get(fn(
                self.params, jax.tree.map(cut, self.feats),
                jax.tree.map(cut, self.masks), *map(cut, tokens))))
        return tuple(np.concatenate(x, 0) for x in zip(*out))

    def _program(self) -> np.ndarray:
        """The program's own step forced along the sampled captions: encode
        (prefill), then ``decode_step`` on the carry a token at a time, PAD
        and BOS forbidden as its decode loops forbid them."""
        import jax
        import jax.numpy as jnp

        from cst_captioning_tpu.decoding.common import forbid_special, row_logprobs
        from cst_captioning_tpu.models.captioner import CaptionModel, EncoderOutput

        model = self.model

        def forced(p, f, m, tokens):
            enc = model.apply(p, f, m, method=CaptionModel.encode)
            bank = EncoderOutput(enc.memory, enc.memory_proj, enc.memory_mask,
                                 carry=())

            def step(state, tok):
                carry, prev = state
                carry, logits = model.apply(p, carry, prev, bank,
                                            method=CaptionModel.decode_step)
                logp = row_logprobs(forbid_special(logits.astype(jnp.float32)))
                return (carry, tok), jnp.take_along_axis(
                    logp, tok[:, None], axis=-1)[:, 0]

            bos = jnp.full(tokens.shape[:1], BOS_ID, jnp.int32)
            _, out = jax.lax.scan(step, (enc.carry, bos), tokens.T)
            return out.T

        fn, rows = jax.jit(forced), int(training.check_value(self.config,
                                                             "follow_rows"))
        cut = lambda x, a: x[a:a + rows]  # noqa: E731
        return np.concatenate([np.asarray(fn(
            self.params, jax.tree.map(lambda x: cut(x, a), self.feats),
            jax.tree.map(lambda x: cut(x, a), self.masks),
            jnp.asarray(cut(self.sampled, a), jnp.int32)))
            for a in range(0, len(self.sampled), rows)])

    def _hold(self, compared, tokens) -> np.ndarray:
        """The numbers read off ``tokens`` [clips, T], the captions that stand
        as emitted (the timed decode's; in a control, the captions the
        reference's search emits at the lower precision): against the float32
        reference's own search (``best``, ``best_score``) and its walk along
        them. -> the walk's log-probabilities of the tokens."""
        logp, edge = self._reference("beam_logprobs", tokens)
        limit = lambda name: training.check_value(self.config, name)  # noqa: E731
        live = tokens != PAD_ID
        either = live | (self.best != PAD_ID)
        score = logp.sum(1)     # 0 after a caption's EOS
        if self.cfg.eval.length_penalty > 0:
            score = score / np.maximum(live.sum(1), 1) ** self.cfg.eval.length_penalty
        compared.at_most("eval_beam_token_mismatch_share",
                         ((tokens != self.best) & either).sum() / either.sum(),
                         limit("beam_token_mismatch_tol"))
        compared.at_most("eval_beam_score_gap_mean",
                         np.abs(self.best_score - score).mean(),
                         limit("beam_score_gap_tol"))
        compared.at_most("eval_beam_rank_gap_max",
                         np.maximum(edge - logp, 0.0)[live].max(),
                         limit("beam_rank_gap_tol"))
        return logp

    def verify(self, compared, log) -> None:
        t0 = time.perf_counter()
        V = self.cfg.model.vocab_size
        compared.at_least("eval_token_id_min", self.tokens.min(), 0)
        compared.at_most("eval_token_id_max", self.tokens.max(), V - 1)
        self.best, self.best_score = self._reference("beam_search")
        self.logp = self._hold(compared, self.sampled)
        live = self.sampled != PAD_ID
        compared.at_most(
            "eval_logprob_mean_abs_diff",
            np.abs(self._program() - self.logp)[live].mean(),
            training.check_value(self.config, "beam_logprob_mean_abs_tol"))
        same = int((self.sampled == self.best).all(1).sum())
        log(f"the reference searched {len(self.rows)} sampled clips of the "
            f"window's last whole pass and read their emitted captions "
            f"({int(live.sum())} tokens, the longest "
            f"{int(live.sum(1).max())}; {same} captions are its own, token "
            f"for token): {time.perf_counter() - t0:.2f}s")

    def control(self, precision: str) -> "training.Compared":
        """The control: the configuration's reference at ``precision`` (the
        nearest below the one the configuration states) in the program's
        place: the captions its beam search emits over the same clips are
        held as the timed decode's are, and its log-probabilities of the
        emitted tokens stand for the program's step's. It has to come out as
        not correct."""
        held = training.Compared()
        captions, _ = self._reference("beam_search", precision=precision)
        self._hold(held, captions)
        low, _ = self._reference("beam_logprobs", self.sampled,
                                 precision=precision)
        held.at_most(
            "eval_logprob_mean_abs_diff",
            np.abs(low - self.logp)[self.sampled != PAD_ID].mean(),
            training.check_value(self.config, "beam_logprob_mean_abs_tol"))
        return held
