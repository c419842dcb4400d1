"""What the training jobs (``jobs/cst.py``, ``jobs/xe.py``) share: the
configuration a cell runs, the corpus and the warm-started policy from
``benchmark/.cache/``, the step clock that takes completion timestamps off the
main thread, and the window that the clock opens and closes.

The program's own loops run the steps (``Trainer.train_xe`` /
``Trainer.train_rl`` in the main thread, stopped by the SIGTERM the program
documents for preemption). The benchmark only listens: a job hands every
dispatched step's loss scalar to :class:`StepClock`, whose thread waits for
the device to finish it (``block_until_ready``) and stamps the time.
"""

from __future__ import annotations

import importlib.util
import json
import os
import queue
import signal
import threading
import time

import numpy as np

from benchmark.corpus import corpus_key, ensure_corpus


class NoCheckpoints:
    """Stands in for ``Trainer.ckpt`` inside a run: nothing is written. The
    program has no field that turns the epoch-end save off; its read-back of
    the state (``device_get``) at every epoch's end still happens."""

    best_value = None

    def save(self, state, value=None, infos=None) -> bool:
        return False

    def save_step(self, state, step, infos=None, extra_files=None) -> str:
        return ""


def experiment_config(config: dict, traffic: dict, seed: int, run_dir: str,
                      chips: int, obs_dir: str = ""):
    """The preset named in the workload, with the configuration's and the
    workload's overrides; ``--seed`` drives data order and sampling keys."""
    from cst_captioning_tpu.config import get_preset

    cfg = get_preset(traffic["preset"])
    over = {k: _tuples(v) for k, v in config.get("overrides", {}).items()}
    over.update({k: _tuples(v) for k, v in traffic.get("overrides", {}).items()})
    over.update({
        "data__shuffle_seed": int(seed),
        "train__seed": int(seed),
        "train__ckpt_dir": os.path.join(run_dir, "ckpt"),
        # validation off: there is no validation split in the run
        "train__eval_every_epochs": 10**9,
        "mesh__num_devices": int(chips),
    })
    if obs_dir:
        over.update({"train__obs": True, "train__obs_dir": obs_dir})
    cfg = cfg.override(**over)
    want = config["model"]
    # the program's values after a JSON round trip, so that a tuple-valued
    # field compares with the file's list
    got = json.loads(json.dumps({k: getattr(cfg.model, k, None) for k in want}))
    if got != want:
        raise SystemExit(f"preset {traffic['preset']} does not have the "
                         f"configuration's sizes: {got} != {want}")
    return cfg


def _tuples(value):
    """A JSON value in the program's spelling: its configuration is hashable,
    so what a file writes as a list is a tuple there."""
    if isinstance(value, list):
        return tuple(_tuples(v) for v in value)
    return value


# ---- what a configuration brings as files ------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MODULES: dict[str, object] = {}


def config_module(config: dict, key: str, needs: str):
    """The module the configuration's file names under ``key`` (``reference``,
    ``costs``): a path from the checkout's root, loaded from that file and by
    no import name, so that an architecture is files and no entry in shared
    code. A configuration that names none, a file that is not there, or a
    module without the function ``needs`` is an error, never a default."""
    path = config.get(key)
    if not path:
        raise SystemExit(f"configuration {config.get('name')!r} names no "
                         f"{key!r} module in its file")
    full = os.path.normpath(os.path.join(ROOT, path))
    if full not in _MODULES:
        if not os.path.isfile(full):
            raise SystemExit(f"configuration {config.get('name')!r}: its "
                             f"{key} module {path} is not in this checkout")
        spec = importlib.util.spec_from_file_location(
            "benchmark_config_" + key + "_" + "".join(
                c if c.isalnum() else "_" for c in path), full)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        _MODULES[full] = module
    module = _MODULES[full]
    if not callable(getattr(module, needs, None)):
        raise SystemExit(f"{path} (the {key} module of configuration "
                         f"{config.get('name')!r}) has no function {needs}")
    return module


def check_value(config: dict, name: str):
    """``checks.<name>.value`` of the configuration's file: a tolerance, or
    the clips or rows a check reads. Each stands there with its ``reason``;
    none is a constant of a job."""
    entry = config.get("checks", {}).get(name)
    if not isinstance(entry, dict) or "value" not in entry or not entry.get("reason"):
        raise SystemExit(f"configuration {config.get('name')!r} states no "
                         f"checks.{name} with a value and a reason")
    return entry["value"]


def reference_logprobs(config: dict, params, feats, masks, tokens, rows: int,
                       forbid_special: bool = False,
                       precision: str = "float32") -> np.ndarray:
    """The configuration's reference, ``token_logprobs(params, model, feats,
    masks, tokens, forbid_special=, precision=)`` with ``model`` the file's
    ``model`` dict, over ``tokens`` [N, T] in blocks of ``rows`` rows, so that
    a deep model's reference fits beside what else the chip holds."""
    import jax

    ref = config_module(config, "reference", "token_logprobs")
    model = config["model"]
    fn = jax.jit(lambda p, f, m, t: ref.token_logprobs(
        p, model, f, m, t, forbid_special=forbid_special, precision=precision))
    tokens = np.asarray(tokens)
    out = []
    for a in range(0, len(tokens), rows):
        cut = lambda x: x[a:a + rows]  # noqa: E731
        out.append(np.asarray(fn(params, jax.tree.map(cut, feats),
                                 jax.tree.map(cut, masks), tokens[a:a + rows])))
    return np.concatenate(out, axis=0)


class Compared:
    """Every number a run compares, beside its limit: ``name -> {"value",
    "rule", "limit", "ok"}``. ``run.py`` prints them as the run's last lines
    on stderr and, under ``compared``, last in the result's line."""

    def __init__(self):
        self.rows: dict[str, dict] = {}

    def _add(self, name, value, rule, limit, ok) -> bool:
        self.rows[name] = {"value": value, "rule": rule, "limit": limit,
                           "ok": bool(ok)}
        return bool(ok)

    def at_most(self, name: str, value: float, limit: float) -> bool:
        return self._add(name, float(value), "<=", limit, float(value) <= limit)

    def at_least(self, name: str, value: float, limit: float) -> bool:
        return self._add(name, float(value), ">=", limit, float(value) >= limit)

    def within(self, name: str, value: float, lo: float, hi: float) -> bool:
        return self._add(name, float(value), "in", [lo, hi],
                         lo <= float(value) <= hi)

    def holds(self, name: str, ok: bool) -> bool:
        """A yes/no finding: 1 where it holds, against the limit 1."""
        return self.at_least(name, 1.0 if ok else 0.0, 1.0)

    @property
    def failed(self) -> list[str]:
        return [k for k, r in self.rows.items() if not r["ok"]]


def open_train_split(cfg, paths: dict, split: str = "train"):
    """The corpus' ``split`` (``corpus.make_corpus`` writes ``train`` only)."""
    from cst_captioning_tpu.data.dataset import CaptionDataset

    return CaptionDataset(
        paths["info_json"],
        {n: paths[n] for n in cfg.model.modality_names},
        split=split, max_frames=cfg.model.max_frames,
        cache_features=cfg.data.cache_features,
    )


def open_trainer(ctx):
    """(config, train split, Trainer) of the run: the cell's corpus and
    document frequencies from the cache, the program's ``Trainer`` on the
    preset with the cell's overrides, checkpoint writes off, weights from the
    configuration's warm-started policy. Events go to ``events_path(ctx)``."""
    from cst_captioning_tpu.train.trainer import Trainer

    config = ctx.config
    paths = ensure_corpus(ctx.cache_dir, config["corpus"])
    policy_dir = ensure_policy(ctx.cache_dir, config, paths, ctx.log)
    cfg = experiment_config(config, ctx.workload["params"], ctx.seed,
                            ctx.run_dir, ctx.chips,
                            obs_dir=ctx.obs_dir if ctx.trace else "")
    ctx.log("set-up: corpus and policy in the cache")
    ds = open_train_split(cfg, paths)
    if cfg.rl.enabled:
        cfg = cfg.override(
            data__cider_df=ensure_cider_df(ctx.cache_dir, config, ds))
    ctx.log("set-up: train split open")
    trainer = Trainer(cfg, ds, None, log_path=events_path(ctx))
    trainer.log.echo = False
    trainer.ckpt = NoCheckpoints()
    trainer.load_params_from(policy_dir, "latest")
    ctx.log("set-up: Trainer built, policy loaded")
    return cfg, ds, trainer


def events_path(ctx) -> str:
    return os.path.join(ctx.run_dir, "events.jsonl")


def train_until_closed(ctx, trainer, ds, clock, phase: str) -> None:
    """Run ``Trainer.train_rl`` / ``train_xe`` (``phase``) in this, the main,
    thread until the clock closes the window and the SIGTERM it sends unwinds
    the loop as a preemption; then release everything."""
    from cst_captioning_tpu.resilience.preempt import Preempted

    try:
        with ctx.annotate(phase):
            getattr(trainer, phase)(epochs=10**6)
        raise SystemExit(f"{phase} returned before the window closed")
    except Preempted:
        pass
    finally:
        clock.finish()
        trainer.close()
        trainer.log.close()
        ds.close()


def window_result(clock: "StepClock", timer: "LoopTimer", chips: int,
                  log) -> dict:
    """What every training job reports of its window."""
    steps = clock.window_steps()
    clips = sum(s[1] for s in steps)
    ends = [clock.t_open] + [s[0] for s in steps][clock.period - 1::clock.period]
    log("epochs in the window took (s): "
        + " ".join(f"{b - a:.3f}" for a, b in zip(ends, ends[1:])))
    return {
        "attempted": len(steps), "steps": steps,
        "end_to_end": {"clips_per_s_per_chip":
                       clips / (clock.t_close - clock.t_open) / chips},
        "input_waits": timer.waits, "turnovers": timer.turnovers,
        "marks": clock.marks,
        "hbm": {"peak_at_open": clock.peak_at_open,
                "peak_at_close": clock.peak_at_close,
                "live_max": max((s[3] for s in steps), default=0)},
    }


def hbm_bytes(chips: int, key: str) -> int:
    """``key`` of ``memory_stats()`` on the fullest of the cell's chips (0
    on a backend that keeps none)."""
    import jax

    return max(int((d.memory_stats() or {}).get(key, 0))
               for d in jax.devices()[:chips])


class LoopTimer:
    """The benchmark's own timer in the main thread: every ``next()`` of the
    batch iterator that the program's loop consumes (the wait on the input
    pipeline), and the stretch between one epoch's loop being left and the
    next one's being entered (the epoch turnover)."""

    def __init__(self):
        self.waits: list[tuple[float, float]] = []      # (t0, t1) of a next()
        self.turnovers: list[tuple[float, float]] = []  # (left, entered)
        self._left: float | None = None

    def entered(self) -> None:
        if self._left is not None:
            self.turnovers.append((self._left, time.perf_counter()))
            self._left = None

    def left(self) -> None:
        self._left = time.perf_counter()

    def batches(self, it, then=None):
        """``it``, with every ``next()`` timed (the one that finds the epoch
        at its end too); ``then()`` is called when the consumer is done."""
        it = iter(it)
        try:
            while True:
                t0 = time.perf_counter()
                try:
                    item = next(it)
                except StopIteration:
                    return
                finally:
                    self.waits.append((t0, time.perf_counter()))
                yield item
        finally:
            if then is not None:
                then()


# ---- the warm-started policy --------------------------------------------------


def ensure_policy(cache_dir: str, config: dict, paths: dict, log) -> str:
    """Checkpoint directory of the configuration's warm-started policy: a few
    hundred XE steps through ``Trainer.train_xe`` on the configuration's own
    corpus with a fixed seed, made on the first run of a checkout. Real SCST
    starts from an XE-trained policy whose captions end; a random one runs
    every lane to the 30-token limit."""
    pol = config["policy"]
    out = os.path.join(cache_dir, "policy-" + corpus_key(
        {"policy": pol, "corpus": config["corpus"], "model": config["model"]}))
    if os.path.exists(os.path.join(out, "latest", "state.msgpack")):
        return out
    import jax

    from cst_captioning_tpu.config import get_preset
    from cst_captioning_tpu.train.trainer import Trainer

    t0 = time.perf_counter()
    cfg = get_preset(pol["preset"]).override(**{
        **config.get("overrides", {}),
        "data__batch_size": pol["batch"], "data__seq_per_vid": 1,
        "data__cache_features": True,
        "data__shuffle_seed": pol["seed"], "train__seed": pol["seed"],
        "train__lr": pol["lr"], "train__lr_decay_every": 0,
        "train__ckpt_dir": out + ".tmp", "mesh__num_devices": 1,
    })
    ds = open_train_split(cfg, paths)
    try:
        trainer = Trainer(cfg, ds, None, use_mesh=False)
        trainer.log.echo = False
        # one save at the end, not the program's one per epoch
        saver, trainer.ckpt = trainer.ckpt, NoCheckpoints()
        epochs = -(-pol["steps"] // trainer.steps_per_epoch)
        trainer.train_xe(epochs=epochs)
        saver.save(jax.device_get(trainer.state), None,
                   infos={"source": "benchmark policy warm start"})
        trainer.close()
    finally:
        ds.close()
    os.replace(out + ".tmp", out)
    log(f"policy warm start: {epochs * trainer.steps_per_epoch} XE steps "
        f"in {time.perf_counter() - t0:.1f}s -> {out}")
    return out


def ensure_cider_df(cache_dir: str, config: dict, ds) -> str:
    """The corpus' CIDEr-D document frequencies, precomputed once per checkout
    as the paper's recipe does (the program's ``data.cider_df`` field): built
    in every run they cost several seconds of Python n-gram counting."""
    from cst_captioning_tpu.metrics.cider import CorpusDF

    path = os.path.join(cache_dir, f"cider_df-{corpus_key(config['corpus'])}.pkl")
    if not os.path.exists(path):
        refs = [[c.split() for c in caps] for caps in ds.gts_pool().values()]
        tmp = path + ".tmp"
        CorpusDF.from_refs(refs).save(tmp)
        os.replace(tmp, path)
    return path


def caption_lengths(tokens) -> np.ndarray:
    """Lengths (EOS excluded) of decoded rows ``[..., T]``; PAD=0 after EOS=2."""
    tok = np.asarray(tokens)
    return ((tok != 0) & (tok != 2)).sum(-1).reshape(-1)


def check_policy_lengths(sampled, greedy, limits: dict,
                         compared: "Compared | None" = None) -> dict:
    """The cell was defined with a policy whose captions end. Fails loudly
    when the policy in use is not that one."""
    s, g = caption_lengths(sampled), caption_lengths(greedy)
    got = {"sampled_len_mean": float(s.mean()),
           "sampled_len_p99": float(np.percentile(s, 99)),
           "greedy_len_min": int(g.min())}
    compared = compared if compared is not None else Compared()
    ok = [compared.within("sampled_len_mean", got["sampled_len_mean"],
                          *limits["sampled_len_mean"]),
          compared.at_most("sampled_len_p99", got["sampled_len_p99"],
                           limits["sampled_len_p99_max"]),
          compared.at_least("greedy_len_min", got["greedy_len_min"], 1)]
    if not all(ok):
        raise SystemExit(f"policy caption lengths {got} outside {limits}: "
                         "the traffic is not the one this cell was defined on")
    return got


# ---- the step clock and the window --------------------------------------------


class StepClock:
    """Completion time of every training step, and the window built on them.

    ``submit(ready, clips)`` is called in the main thread when a step has
    been dispatched; ``ready`` is a device scalar that step produces. The
    clock's thread blocks on it and stamps ``time.perf_counter()``. The
    window opens at the end of the first epoch that ends at or after step
    ``warmup_steps`` (set-up ends there) and closes at the end of the first
    epoch that ends ``seconds`` or more later; then the program is asked to
    stop. Both ends are completions of an epoch's last step (``period`` steps
    an epoch), so the window holds whole epochs — a fixed amount of work,
    every epoch's turnover stall exactly once — and clips / window has no
    rounding and no phase in it. It overruns ``seconds`` by less than an epoch.

    ``on_step(t)``, if given, is called on the clock's thread with the stamp
    of every step that completes inside the window, ``(t_open, t_close]``,
    the closing one before ``on_close``. It must do nothing slow there: the
    traced stretch (``run.Stretch``) records the time and wakes its thread.
    """

    def __init__(self, warmup_steps: int, seconds: float, on_open=None,
                 on_close=None, period: int = 1, chips: int = 1, on_step=None):
        self.warmup_steps, self.seconds = int(warmup_steps), float(seconds)
        self.period, self.chips = max(int(period), 1), int(chips)
        self.on_open, self.on_close, self.on_step = on_open, on_close, on_step
        # (t_done, clips, t_dispatch, bytes_in_use of the fullest chip then)
        self.done: list[tuple[float, float, float, int]] = []
        self.marks: dict[str, list[float]] = {}    # name -> completion times
        self.peak_at_open = self.peak_at_close = 0
        self.t_open: float | None = None
        self.t_close: float | None = None
        self.error: BaseException | None = None
        self._q: queue.Queue = queue.Queue()
        self._thread = threading.Thread(target=self._run, name="bench-clock",
                                        daemon=True)
        self._thread.start()

    def submit(self, ready, clips: float) -> None:
        self._q.put((ready, "", float(clips), time.perf_counter()))

    def mark(self, ready, name: str) -> None:
        """Stamp the completion of ``ready`` under ``name``: something other
        than a step's end (the decode's rollouts becoming ready). Dispatch
        order is the device's order, so one thread waits for both in turn."""
        self._q.put((ready, name, 0.0, 0.0))

    def finish(self) -> None:
        self._q.put(None)
        self._thread.join()
        if self.error is not None:
            raise self.error

    def _run(self) -> None:
        import jax

        try:
            while (item := self._q.get()) is not None:
                ready, mark, clips, t_dispatch = item
                jax.block_until_ready(ready)
                t = time.perf_counter()
                if mark:
                    self.marks.setdefault(mark, []).append(t)
                    continue
                self.done.append((t, clips, t_dispatch,
                                  hbm_bytes(self.chips, "bytes_in_use")))
                if (self.on_step and self.t_open is not None
                        and self.t_close is None):
                    self.on_step(t)
                if len(self.done) % self.period:
                    continue        # not an epoch's last step
                if self.t_open is None:
                    if len(self.done) >= self.warmup_steps:
                        self.t_open = t
                        self.peak_at_open = hbm_bytes(
                            self.chips, "peak_bytes_in_use")
                        if self.on_open:
                            self.on_open(t)
                elif self.t_close is None and t - self.t_open >= self.seconds:
                    self.t_close = t
                    self.peak_at_close = hbm_bytes(
                        self.chips, "peak_bytes_in_use")
                    if self.on_close:
                        self.on_close(t)
                    # the program's documented stop: SIGTERM ends the epoch
                    # at the next batch boundary and drains the pipeline
                    os.kill(os.getpid(), signal.SIGTERM)
        except Exception as e:  # the job's finish() raises it in the main thread
            self.error = e
            os.kill(os.getpid(), signal.SIGTERM)

    def window_steps(self) -> list[tuple[float, float, float, int]]:
        """Steps completed inside (t_open, t_close]."""
        if self.t_open is None or self.t_close is None:
            raise RuntimeError("the window never closed")
        return [s for s in self.done if self.t_open < s[0] <= self.t_close]


def read_spans(obs_dir: str) -> list[dict]:
    """Finished spans of the program's obs stream (``events.jsonl``), with
    start and end on ``time.time()``'s clock (``ts`` is stamped at the end)."""
    path = os.path.join(obs_dir, "events.jsonl")
    spans = []
    if not os.path.exists(path):
        return spans
    with open(path) as f:
        for line in f:
            try:
                ev = json.loads(line)
            except ValueError:
                continue
            if ev.get("event") == "span":
                spans.append({"name": ev["name"], "t1": ev["ts"],
                              "t0": ev["ts"] - ev["dur"], "dur": ev["dur"],
                              "thread": ev.get("thread", "")})
    return spans
