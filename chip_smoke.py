#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the captioner still starts on the chip.

Drives the system's main path ONCE, in ONE process, through the entry points
a user calls (``cli.train.main``, ``cli.eval.main``, ``CaptionService``), at
the unmodified widths of the paper's presets (``msrvtt_xe_attention`` /
``msrvtt_cst_consensus`` / ``msrvtt_eval_beam5``: V=9000, resnet 2048 + c3d
500, d=512, d_att=256, 28 frames, 30 tokens, bf16, K=5, SCB baseline,
CIDEr-D + BLEU4 reward). Weights are random from ``--seed``; the corpus is
synthetic (``data/synthetic.py``), written at run time.

    python chip_smoke.py              # one chip: xe, cst, cst_large, eval,
                                      #           serve, kernels
    python chip_smoke.py --chips 4    # four chips: ONLY the data-parallel XE
                                      # step + SCST cycle and their one-device
                                      # comparison

Every phase prints one JSON line (name, ok, compile seconds, run seconds,
the check it made). The LAST line of stdout is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as jax reports it. Without a TPU the script prints
``"ok": false`` and exits 1 — it never carries on on the CPU. A failed phase
is reported, the later phases still run (each chip run should say as much
as it can), and the exit code is 1. Claims nothing about speed: the seconds
are there to show where a cold run's time goes and that a second run hits
the compile cache (``utils/compile_cache.py``).
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time
import traceback

import numpy as np

# eval lanes-vs-reference bar: under bf16 near-tie argmax flips cost a few
# tokens in a hundred; a wrong program costs most of them
TIE_NOISE_FLOOR = 0.9
# served-vs-offline and kernel-vs-XLA token parity are REPORTED (the model is
# barely trained, bf16, and the kernels compute in f32: near-tie argmax flips
# then diverge a whole caption). A wrong program — wrong rows, wrong pages —
# agrees at chance level, far below this
WRONG_PROGRAM_FLOOR = 0.5
# the contract's wall-clock limit, and the latest a cold cst_large may start
# (two B=1792 programs compile in about two minutes)
TIME_LIMIT_S = 1200.0
LARGE_PHASE_LATEST_START_S = 600.0


@dataclasses.dataclass(frozen=True)
class Scale:
    """What a run is cut to. ``PRESET`` is what the chip runs: no model
    override at all. The CPU control-flow test (tests/test_chip_smoke.py)
    passes a tiny one; nothing else differs between the two."""

    model_sets: tuple[str, ...] = ()        # --set overrides shrinking the model
    modalities: tuple[tuple[str, int], ...] = (("resnet", 2048), ("c3d", 500))
    max_frames: int = 28
    vocab_words: int = 8996                 # + 4 specials = the presets' 9000
    train_videos: int = 3584                # two steps at the large batch
    val_videos: int = 64
    test_videos: int = 64
    batch: int = 64
    large_batch: int = 1792                 # the benchmark cells' operating
    large_chunks: int = 5                   # point (benchmark/workloads/)
    serve_requests: int = 32
    serve_capacity: int = 8
    serve_frames: tuple[int, ...] = (5, 20)
    frame_bucket: int = 4                   # page = 2 modalities x 4 = 8 slots
    mesh_batches: int = 3                   # --chips 4: XE steps compared


PRESET = Scale()


class CompileClock:
    """Seconds spent in the backend compiler (XLA + Mosaic), from jax's own
    monitoring event around ``compile_or_get_cached`` — so a persistent-
    cache hit shows as seconds that fall away. Tracing and lowering are not
    counted (nested jits would count twice) and land in a phase's run
    seconds."""

    _installed: "CompileClock | None" = None

    def __init__(self):
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_kw) -> None:
        if event.endswith("backend_compile_duration"):
            self.seconds += max(float(duration), 0.0)

    @classmethod
    def install(cls) -> "CompileClock":
        # one listener per process: jax keeps listeners for good
        if cls._installed is None:
            from jax import monitoring

            cls._installed = cls()
            monitoring.register_event_duration_secs_listener(cls._installed)
        return cls._installed


class Smoke:
    """One run: the corpus, the checkpoints the phases hand each other, the
    compile clock, and the phase ledger."""

    def __init__(self, scale: Scale, seed: int, work: str):
        self.scale, self.seed, self.work = scale, seed, work
        self.t0 = time.perf_counter()
        self.failed: list[str] = []
        self._compile = CompileClock.install()
        self.paths: dict = {}
        self._model_params = None
        self.served = None     # serve phase -> kernels phase: (reqs, report)

    # ---- plumbing -----------------------------------------------------------

    def elapsed(self) -> float:
        return time.perf_counter() - self.t0

    def run_phase(self, name: str, fn) -> None:
        """Run one phase; print its line. A failure is reported with the
        traceback on stderr and remembered — never swallowed."""
        c0, t0 = self._compile.seconds, time.perf_counter()
        line = {"phase": name, "ok": True}
        try:
            with contextlib.redirect_stdout(sys.stderr):
                line.update(fn(self) or {})
        except Exception as e:  # reported below, fails the run
            traceback.print_exc()
            line.update(ok=False, error=f"{type(e).__name__}: {e}"[:600])
        if not line["ok"]:
            self.failed.append(name)
        compile_s = self._compile.seconds - c0
        line["compile_s"] = round(compile_s, 1)
        line["run_s"] = round(time.perf_counter() - t0 - compile_s, 1)
        print(json.dumps(line, default=float), flush=True)

    def dir(self, name: str) -> str:
        return os.path.join(self.work, name)

    def common_args(self, batch: int | None = None) -> list[str]:
        feats = [
            x for name, _ in self.scale.modalities
            for x in ("--feature", f"{name}={self.paths[name]}")
        ]
        sets = [
            f"model__vocab_size={self.paths['vocab_size']}",
            f"data__batch_size={batch or self.scale.batch}",
            f"train__seed={self.seed}",
            # one chip's path even where more are visible
            "mesh__num_devices=1",
            *self.scale.model_sets,
        ]
        return [
            "--info-json", self.paths["info_json"], *feats,
            *(x for s in sets for x in ("--set", s)),
        ]

    def events(self, log: str) -> list[dict]:
        with open(log) as f:
            return [json.loads(line) for line in f]

    def dataset(self, split: str):
        from cst_captioning_tpu.data.dataset import CaptionDataset

        return CaptionDataset(
            self.paths["info_json"],
            {n: self.paths[n] for n, _ in self.scale.modalities},
            split, self.scale.max_frames,
        )

    def model_cfg(self):
        from cst_captioning_tpu.cli.common import parse_overrides
        from cst_captioning_tpu.config import get_preset

        sets = [f"model__vocab_size={self.paths['vocab_size']}",
                *self.scale.model_sets]
        return get_preset("msrvtt_cst_consensus").override(
            **parse_overrides(sets)
        ).model

    def load_params(self, ckpt_dir: str, name: str):
        """(model, checkpointed params as host arrays). The restore only
        needs the tree's structure, so the template stays abstract."""
        import jax

        from cst_captioning_tpu.ckpt import load_params
        from cst_captioning_tpu.models import CaptionModel

        model = CaptionModel(mc := self.model_cfg())
        feats = {n: np.zeros((2, mc.max_frames, d), np.float32)
                 for n, d in mc.modalities}
        masks = {n: np.ones((2, mc.max_frames), np.float32)
                 for n, _ in mc.modalities}
        labels = np.zeros((2, mc.max_len), np.int32)
        template = jax.eval_shape(
            lambda: model.init(jax.random.key(0), feats, masks, labels)
        )
        return model, load_params(ckpt_dir, name, template)

    def model_and_params(self):
        """(model, device params) of the newest checkpoint a phase left."""
        import jax

        if self._model_params is None:
            for ckpt, name in ((self.dir("rl_ckpt"), "latest"),
                               (self.dir("xe_ckpt"), "best")):
                if os.path.isdir(os.path.join(ckpt, name)):
                    model, params = self.load_params(ckpt, name)
                    self._model_params = (model, jax.device_put(params))
                    break
            else:
                raise RuntimeError("no checkpoint: the xe phase left none")
        return self._model_params

    def test_batch(self):
        """The test split's first batch as device arrays (feats, masks)."""
        from cst_captioning_tpu.data.batcher import Batcher
        from cst_captioning_tpu.train.steps import batch_arrays

        ds = self.dataset("test")
        try:
            b = next(iter(Batcher(
                ds, batch_size=self.scale.batch,
                max_len=self.model_cfg().max_len, mode="video",
            ).epoch(shuffle=False)))
        finally:
            ds.close()
        feats, masks, labels, *_ = batch_arrays(b)
        return feats, masks, labels


class CheckFailed(Exception):
    """A phase's own check did not hold (as opposed to the program raising)."""


def _require(cond: bool, what: str) -> None:
    if not cond:
        raise CheckFailed(what)


def _finite(xs) -> bool:
    return bool(len(xs)) and bool(np.all(np.isfinite(np.asarray(xs, float))))


def _close(a: float, b: float, rel: float = 2e-2, floor: float = 0.0) -> bool:
    return abs(a - b) <= rel * max(abs(b), floor)


def _match(a, b) -> float:
    """Token match fraction."""
    return float(np.mean(np.asarray(a) == np.asarray(b)))


# ---- corpus -----------------------------------------------------------------


def build_corpus(smoke: Smoke) -> None:
    """Seeded synthetic corpus at the scale's widths (template captions, so
    the consensus reward has structure to point at)."""
    from cst_captioning_tpu.data import make_synthetic_dataset

    sc = smoke.scale
    n = sc.train_videos + sc.val_videos + sc.test_videos
    smoke.paths = make_synthetic_dataset(
        smoke.dir("data"), num_videos=n, num_topics=12,
        vocab_words=sc.vocab_words, captions_per_video=5,
        caption_len=(5, 13), modalities=dict(sc.modalities),
        max_frames=sc.max_frames,
        # +0.5: the splits are cut with int(); keep them exact
        splits=((sc.train_videos + 0.5) / n, (sc.val_videos + 0.5) / n),
        seed=smoke.seed, caption_style="template", template_noise=0.35,
        feature_noise=0.05,
    )
    with open(smoke.paths["info_json"]) as f:
        smoke.paths["vocab_size"] = len(json.load(f)["vocab"])


# ---- phases (one chip) ------------------------------------------------------


def phase_xe(smoke: Smoke) -> dict:
    """Teacher-forced steps through ``Trainer``: loss finite and falling."""
    from cst_captioning_tpu.cli.train import main as train_main

    log = smoke.dir("xe.jsonl")
    train_main([
        "--preset", "msrvtt_xe_attention", *smoke.common_args(),
        "--set", "train__epochs=1", "--set", "train__lr=5e-4",
        "--set", "train__log_every_steps=1",
        "--set", f"train__ckpt_dir='{smoke.dir('xe_ckpt')}'",
        "--log-jsonl", log,
    ])
    ev = smoke.events(log)
    losses = [e["loss"] for e in ev if e["event"] == "xe_step"]
    _require(_finite(losses), f"xe losses finite ({len(losses)} steps)")
    q = max(len(losses) // 4, 1)
    first, last = float(np.mean(losses[:q])), float(np.mean(losses[-q:]))
    _require(last < first, f"xe loss falls ({first:.3f} -> {last:.3f})")
    val = [e["cider_d"] for e in ev if e["event"] == "validate"]
    _require(_finite(val), "greedy validation CIDEr-D finite")
    _require(os.path.isdir(os.path.join(smoke.dir("xe_ckpt"), "best")),
             "best checkpoint written")
    return {
        "steps": len(losses), "loss_first": round(first, 4),
        "loss_last": round(last, 4), "val_cider_d": round(val[-1], 4),
        "check": "loss finite and falling; validation scored; ckpt written",
    }


def _rl_phase(smoke: Smoke, log: str, ckpt: str, extra: list[str],
              batch: int | None = None) -> dict:
    from cst_captioning_tpu.cli.train import main as train_main

    train_main([
        "--preset", "msrvtt_cst_consensus", *smoke.common_args(batch),
        "--skip-xe",
        "--set", f"rl__init_from='{smoke.dir('xe_ckpt')}'",
        "--set", "rl__epochs=1", "--set", "train__log_every_steps=1",
        "--set", f"train__ckpt_dir='{ckpt}'", *extra,
        "--log-jsonl", log,
    ])
    ev = smoke.events(log)
    steps = [e for e in ev if e["event"] == "rl_step"]
    _require([e for e in ev if e["event"] == "handoff"] != [],
             "XE -> RL handoff happened")
    _require(_finite([e["reward"] for e in steps]), "rewards finite")
    _require(_finite([e["rl_loss"] for e in steps]), "rl losses finite")
    _require(_finite([e["grad_norm"] for e in steps]), "grad norms finite")
    scorer = [e for e in ev if e["event"] == "reward_scorer"][-1]
    # RewardComputer asks for the native scorer by default: not getting it
    # is a failure here, not a slower run
    _require(scorer["scorer"] == "native",
             f"native reward scorer loaded ({scorer['error']})")
    return {
        "steps": len(steps), "scorer": scorer["scorer"],
        "reward_mean": round(float(np.mean([e["reward"] for e in steps])), 4),
    }


def phase_cst(smoke: Smoke) -> dict:
    """SCST steps through ``SCSTTrainer`` from the XE checkpoint, pipelined
    default: reward and loss finite, params moved."""
    import jax

    out = _rl_phase(smoke, smoke.dir("cst.jsonl"), smoke.dir("rl_ckpt"), [])
    _, before = smoke.load_params(smoke.dir("xe_ckpt"), "best")
    _, after = smoke.load_params(smoke.dir("rl_ckpt"), "latest")
    moved = max(
        float(np.max(np.abs(np.asarray(a, np.float32)
                            - np.asarray(b, np.float32))))
        for a, b in zip(jax.tree.leaves(after), jax.tree.leaves(before))
    )
    _require(np.isfinite(moved) and moved > 0.0, "params changed")
    out.update(
        param_max_abs_change=moved,
        check="reward/loss/grad-norm finite; native scorer; params changed",
    )
    return out


def phase_cst_large(smoke: Smoke) -> dict:
    """Two SCST steps at the first benchmark cell's operating point, so an
    out-of-memory failure surfaces now."""
    import jax

    if smoke.elapsed() > LARGE_PHASE_LATEST_START_S:
        # not a pass: said out loud, and the run's ok stays honest about it
        return {
            "skipped": f"{smoke.elapsed():.0f}s already spent; a cold "
                       f"B={smoke.scale.large_batch} compile would cross "
                       f"the {TIME_LIMIT_S:.0f}s limit",
        }
    ckpt = smoke.dir("large_ckpt")
    try:
        out = _rl_phase(
            smoke, smoke.dir("cst_large.jsonl"), ckpt,
            ["--set", f"rl__update_chunks={smoke.scale.large_chunks}",
             "--set", "train__eval_every_epochs=1000000"],
            batch=smoke.scale.large_batch,
        )
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    want = smoke.scale.train_videos // smoke.scale.large_batch
    _require(out["steps"] == want, f"{want} large steps ran ({out['steps']})")
    stats = jax.devices()[0].memory_stats() or {}
    out.update(
        batch=smoke.scale.large_batch, update_chunks=smoke.scale.large_chunks,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        bytes_limit=stats.get("bytes_limit"),
        check="two steps at the benchmark batch fit and stay finite",
    )
    return out


def _caption_tokens(captions: dict, vids: list[str], T: int) -> list:
    return [(captions[v].split() + [""] * T)[:T] for v in vids]


def phase_eval(smoke: Smoke) -> dict:
    """Beam-5 over the test split through the evaluator CLI, metrics JSON
    written; default ``beam_impl='lanes'`` against ``'reference'``."""
    from cst_captioning_tpu.cli.eval import main as eval_main

    ckpt, name = smoke.dir("rl_ckpt"), "latest"
    if not os.path.isdir(os.path.join(ckpt, name)):
        ckpt, name = smoke.dir("xe_ckpt"), "best"
    results = {}
    for impl in ("lanes", "reference"):
        path = smoke.dir(f"results_{impl}.json")
        eval_main([
            "--preset", "msrvtt_eval_beam5", *smoke.common_args(),
            "--ckpt-dir", ckpt, "--ckpt-name", name, "--split", "test",
            "--set", f"eval__beam_impl='{impl}'", "--results-json", path,
        ])
        with open(path) as f:
            results[impl] = json.load(f)
    lanes, ref = results["lanes"], results["reference"]
    _require(len(lanes["captions"]) == smoke.scale.test_videos,
             "one caption per test video")
    _require(_finite(list(lanes["metrics"].values())), "metrics finite")
    vids = sorted(lanes["captions"])
    T = smoke.model_cfg().max_len
    frac = _match(_caption_tokens(lanes["captions"], vids, T),
                  _caption_tokens(ref["captions"], vids, T))
    _require(frac >= TIE_NOISE_FLOOR,
             f"lanes vs reference token match {frac:.4f} >= {TIE_NOISE_FLOOR}")
    return {
        "captions": len(vids),
        "cider_d": round(lanes["metrics"]["CIDEr-D"], 4),
        "lanes_vs_reference_token_match": round(frac, 4),
        "lanes_vs_reference_captions_equal": sum(
            lanes["captions"][v] == ref["captions"][v] for v in vids
        ),
        "check": f"metrics JSON written and finite; lanes vs reference "
                 f">= {TIE_NOISE_FLOOR}",
    }


def _requests(smoke: Smoke):
    from cst_captioning_tpu.serving import ClipRequest, TrafficSpec, make_trace
    from cst_captioning_tpu.serving.traffic import synth_request_features

    trace = make_trace(TrafficSpec(
        kind="poisson", rate_rps=16.0, num_requests=smoke.scale.serve_requests,
        seed=smoke.seed, frame_choices=smoke.scale.serve_frames,
    ))
    reqs = []
    for item in trace.items:
        feats, masks = synth_request_features(item, smoke.scale.modalities)
        reqs.append(ClipRequest(
            req_id=item.req_id, feats=feats, masks=masks, seed=item.seed,
            arrival_s=item.arrival_s,
        ))
    return reqs


def _serve(smoke: Smoke, model, params, reqs):
    from cst_captioning_tpu.serving import CaptionService

    svc = CaptionService(
        model, params, capacity=smoke.scale.serve_capacity, num_rollouts=2,
        frame_bucket=smoke.scale.frame_bucket,
    )
    report = svc.serve(reqs, realtime=True)
    _require(report.completed == len(reqs) and not report.drained,
             f"every request completed ({report.completed}/{len(reqs)})")
    V = model.cfg.vocab_size
    for res in report.results.values():
        _require(res.tokens.min() >= 0 and res.tokens.max() < V,
                 "served token ids in range")
        _require(np.all(np.isfinite(res.logprobs))
                 and res.logprobs.max() <= 1e-6, "served logprobs sane")
    return svc, report


def phase_serve(smoke: Smoke) -> dict:
    """``CaptionService`` answers a few tens of open-loop requests; served
    tokens against the offline decode of the same clip."""
    import jax

    from cst_captioning_tpu.decoding.fused import fused_decode

    model, params = smoke.model_and_params()
    reqs = _requests(smoke)
    _, report = _serve(smoke, model, params, reqs)
    smoke.served = (reqs, report)

    F = model.cfg.max_frames
    offline = jax.jit(lambda p, f, m, r: fused_decode(
        model, p, f, m, r, num_rollouts=2,
    ))
    fracs, exact = [], 0
    for req in reqs:
        pad = F - req.num_frames
        f1 = {n: np.pad(x, ((0, pad), (0, 0)))[None]
              for n, x in req.feats.items()}
        m1 = {n: np.pad(x, ((0, pad),))[None] for n, x in req.masks.items()}
        g, _, s, _ = jax.device_get(
            offline(params, f1, m1, jax.random.key(req.seed))
        )
        tok = np.concatenate([g, s[:, 0]], axis=0)
        served = report.results[req.req_id].tokens
        fracs.append(_match(served, tok))
        exact += bool(np.array_equal(served, tok))
    frac = float(np.mean(fracs))
    _require(frac >= WRONG_PROGRAM_FLOOR,
             f"served vs offline token match {frac:.4f}")
    return {
        "requests": len(reqs), "strides": report.strides,
        "served_vs_offline_token_match": round(frac, 4),
        "served_equals_offline": exact == len(reqs),
        "requests_token_exact": exact,
        "check": "every request completed, tokens/logprobs sane; "
                 "served-vs-offline parity reported",
    }


def phase_kernels(smoke: Smoke) -> dict:
    """The decode, beam and serve programs with ``decode_impl='pallas'``:
    ``tpu_custom_call`` really in each compiled program, token parity with
    the XLA path reported. A kernel the chip's compiler refuses is named
    ``refused`` on its own line and fails the run — nothing runs a composite
    under a kernel's name."""
    import jax

    from cst_captioning_tpu.decoding import beam_search
    from cst_captioning_tpu.models import CaptionModel
    from cst_captioning_tpu.rl.scst import make_rl_decode

    model, params = smoke.model_and_params()
    m_pal = CaptionModel(dataclasses.replace(model.cfg, decode_impl="pallas"))
    feats, masks, _labels = smoke.test_batch()
    rng = jax.random.key(smoke.seed)
    K, T = 5, model.cfg.max_len
    on_chip = jax.default_backend() == "tpu"
    out: dict = {}
    refused: list[str] = []

    def refuse(name, err):
        """The compiler's (or the build-time check's) refusal, named."""
        reason = " ".join(str(err).split())[:300]
        print(f"kernel {name}: refused {reason}", file=sys.__stdout__,
              flush=True)
        refused.append(name)

    def both(name, make, *args, compare):
        """Compile + run ``make(model)`` for the XLA and the kernel model;
        the kernel program must hold a Mosaic call (on the chip)."""
        ref = make(model).lower(*args).compile()(*args)
        try:
            compiled = make(m_pal).lower(*args).compile()
        except Exception as e:
            return refuse(name, e)
        has_call = "tpu_custom_call" in compiled.as_text()
        _require(has_call or not on_chip,
                 f"{name}: tpu_custom_call in the compiled program")
        out[name] = {"tpu_custom_call": has_call,
                     **compare(jax.device_get(ref),
                               jax.device_get(compiled(*args)))}

    def tokens(ref, got):
        frac = _match(np.concatenate([np.ravel(x) for x in
                                      jax.tree.leaves(ref)]),
                      np.concatenate([np.ravel(x) for x in
                                      jax.tree.leaves(got)]))
        _require(frac >= WRONG_PROGRAM_FLOOR,
                 f"kernel vs XLA token match {frac:.4f}")
        return {"token_match": round(frac, 4)}

    # step kernel: the K-rollout sampling decode (SCB has no greedy lane)
    both("decode_step",
         lambda m: make_rl_decode(m, K, max_len=T, with_greedy=False),
         params, feats, masks, rng, compare=tokens)
    # stride kernel: the fused (1+K)-lane decode (greedy-baseline SCST)
    both("decode_stride",
         lambda m: make_rl_decode(m, K, max_len=T, with_greedy=True),
         params, feats, masks, rng, compare=tokens)
    # beam kernel: the evaluator's lane-batched beam-5
    both("beam",
         lambda m: jax.jit(lambda p, f, k: beam_search(
             m, p, f, k, beam_size=5, max_len=T)[0]),
         params, feats, masks, compare=tokens)

    # paged stride kernel: CaptionService, same requests as the serve phase
    _require(smoke.served is not None, "serve phase results to compare with")
    reqs, xla_report = smoke.served
    try:
        svc, report = _serve(smoke, m_pal, params, reqs)
    except CheckFailed:
        raise
    except Exception as e:  # the stride program compiles inside serve()
        refuse("serve_paged_stride", e)
    else:
        has_call = "tpu_custom_call" in (svc.stride_program_text() or "")
        _require(has_call or not on_chip,
                 "serve: tpu_custom_call in the stride program")
        _require(svc.paged, "serving reads pages in-kernel by default")
        ids = sorted(report.results)
        out["serve_paged_stride"] = {"tpu_custom_call": has_call, **tokens(
            [xla_report.results[i].tokens for i in ids],
            [report.results[i].tokens for i in ids],
        )}
    _require(not refused, f"kernels refused by the compiler: {refused}")
    out["check"] = ("tpu_custom_call in each kernel program; token parity "
                    "with the XLA path reported")
    return out


ONE_CHIP_PHASES = (
    ("xe", phase_xe), ("cst", phase_cst), ("cst_large", phase_cst_large),
    ("eval", phase_eval), ("serve", phase_serve), ("kernels", phase_kernels),
)


# ---- --chips 4: the data-parallel path and its one-device comparison ---------


def _spread(x) -> dict:
    """Where an array really lives: devices holding it, bytes on each."""
    per_dev: dict[int, int] = {}
    for s in x.addressable_shards:
        per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
    return {"devices": len(x.sharding.device_set),
            "bytes_per_device": sorted(per_dev.values())}


def _tree_gap(a, b) -> dict:
    import jax

    la = [np.asarray(x, np.float32) for x in jax.tree.leaves(jax.device_get(a))]
    lb = [np.asarray(x, np.float32) for x in jax.tree.leaves(jax.device_get(b))]
    gap = max(float(np.max(np.abs(x - y))) for x, y in zip(la, lb))
    scale = max(float(np.max(np.abs(x))) for x in la)
    return {"param_max_abs_diff": gap, "param_max_abs": scale}


def _mesh_setup(smoke: Smoke):
    """(trainer on the full mesh, trainer on one device): same seed, same
    data, dropout off so the two runs are the same computation up to the
    order of a sum (the per-shard dropout keys differ by construction)."""
    from cst_captioning_tpu.cli.common import parse_overrides
    from cst_captioning_tpu.config import get_preset
    from cst_captioning_tpu.train.trainer import Trainer

    sets = [f"model__vocab_size={smoke.paths['vocab_size']}",
            f"data__batch_size={smoke.scale.batch}",
            f"train__seed={smoke.seed}", "model__dropout=0.0",
            *smoke.scale.model_sets]
    cfg = get_preset("msrvtt_cst_consensus").override(**parse_overrides(sets))
    ds = smoke.dataset("train")
    mesh_tr = Trainer(cfg.override(
        train__ckpt_dir=smoke.dir("mesh_ckpt")), ds, None)
    one_tr = Trainer(cfg.override(
        train__ckpt_dir=smoke.dir("one_ckpt"), mesh__num_devices=1), ds, None)
    _require(mesh_tr.mesh is not None and one_tr.mesh is None,
             "one trainer on the mesh, one on a single device")
    return cfg, ds, mesh_tr, one_tr


def phase_mesh_xe(smoke: Smoke) -> dict:
    """The sharded XE step on every visible chip vs the same global batches
    on one device: losses and params agree, arrays are really spread."""
    import jax

    from cst_captioning_tpu.train.mesh import shard_batch
    from cst_captioning_tpu.train.steps import batch_arrays

    cfg, ds, mesh_tr, one_tr = smoke.mesh = _mesh_setup(smoke)
    n = len(jax.devices())
    _require(mesh_tr.mesh.shape["data"] == n, f"'data' axis spans {n} chips")
    losses, gnorms = [], []
    batches = mesh_tr.batcher.epoch(shuffle=False)
    for _, b in zip(range(smoke.scale.mesh_batches), batches):
        arrays = (b.feats, b.feat_masks, b.labels, b.mask, b.weights)
        placed = shard_batch(mesh_tr.mesh, arrays)
        mesh_tr.state, m4 = mesh_tr.xe_step(mesh_tr.state, *placed)
        one_tr.state, m1 = one_tr.xe_step(one_tr.state, *batch_arrays(b))
        losses.append((float(m4["loss"]), float(m1["loss"])))
        gnorms.append((float(m4["grad_norm"]), float(m1["grad_norm"])))
    gap = _tree_gap(mesh_tr.state.params, one_tr.state.params)
    loss_gap = max(abs(a - b) for a, b in losses)
    _require(_finite([x for pair in losses + gnorms for x in pair]),
             "losses and grad norms finite")
    _require(loss_gap <= 2e-2 * max(abs(losses[0][1]), 1.0),
             f"mesh and one-device losses agree (gap {loss_gap:.2e})")
    # the gradient's SCALE, which Adam's update is blind to: a gradient
    # summed twice over the mesh moves the params like the right one
    _require(all(_close(a, b) for a, b in gnorms),
             f"mesh and one-device grad norms agree ({gnorms})")
    _require(gap["param_max_abs_diff"] <= 2e-2 * gap["param_max_abs"],
             f"params agree ({gap})")
    leaf = jax.tree.leaves(mesh_tr.state.params)[0]
    batch_leaf = jax.tree.leaves(placed)[0]
    p, bt = _spread(leaf), _spread(batch_leaf)
    _require(p["devices"] == n and bt["devices"] == n
             and len(bt["bytes_per_device"]) == n,
             f"params and batch live on all {n} devices ({p}, {bt})")
    return {
        "devices": n, "losses_mesh_vs_one": losses,
        "grad_norms_mesh_vs_one": gnorms,
        "loss_max_abs_diff": loss_gap, **gap,
        "param_leaf": p, "batch_leaf": bt,
        "check": "sharded XE step == one-device step on the same global "
                 "batch; params replicated on and batch split over all chips",
    }


def phase_mesh_scst(smoke: Smoke) -> dict:
    """The SCST cycle on the mesh — sharded decode, host consensus reward,
    sharded REINFORCE update — and the SAME rollouts through the one-device
    update (the shards draw their own sampling streams, so only the update
    is comparable)."""
    import jax

    from jax.sharding import PartitionSpec as P

    from cst_captioning_tpu.rl import RewardComputer, SCSTTrainer
    from cst_captioning_tpu.rl.rewards import scb_baseline
    from cst_captioning_tpu.train import multihost
    from cst_captioning_tpu.train.mesh import shard_batch

    cfg, ds, mesh_tr, one_tr = smoke.mesh
    reward = RewardComputer(
        ds.vocab, ds.gts_pool(),
        cider_weight=cfg.rl.reward_cider_weight,
        bleu_weight=cfg.rl.reward_bleu4_weight,
        bleu_scale=cfg.rl.reward_bleu4_scale,
    )
    _require(reward.scorer == "native",
             f"native reward scorer loaded ({reward.native_error})")
    T = cfg.model.max_len
    mesh_scst = SCSTTrainer(mesh_tr.model, reward, cfg.rl, mesh=mesh_tr.mesh,
                            max_len=T, guard=True)
    one_scst = SCSTTrainer(one_tr.model, reward, cfg.rl, mesh=None,
                           max_len=T, guard=True)
    b = next(iter(mesh_tr.batcher.epoch(shuffle=False)))
    feats4, masks4 = shard_batch(mesh_tr.mesh, (b.feats, b.feat_masks))
    rng = jax.random.key(smoke.seed + 1)

    # the cycle, on the mesh
    state4, m4 = mesh_scst.train_step(
        mesh_tr.state, feats4, masks4, b.video_ids, rng
    )
    # the same rollouts through both updates, from the same starting state
    _, samples = mesh_scst.decode(mesh_tr.state.params, feats4, masks4, rng)
    samples_np = np.asarray(jax.device_get(samples))
    K, B, _ = samples_np.shape
    r_kb = reward(b.video_ids, samples_np.reshape(K * B, -1)).reshape(K, B)
    adv = np.asarray(r_kb - scb_baseline(r_kb), np.float32)
    valid = np.ones((B,), np.float32)
    mesh = mesh_tr.mesh
    new4, u4 = mesh_scst.update(
        mesh_tr.state, feats4, masks4, samples,
        multihost.from_host_local(adv, mesh, P(None, "data")),
        multihost.from_host_local(valid, mesh, P("data")),
    )
    new1, u1 = one_scst.update(
        one_tr.state, b.feats, b.feat_masks, samples_np, adv, valid
    )
    gap = _tree_gap(new4.params, new1.params)
    l4, l1 = float(u4["rl_loss"]), float(u1["rl_loss"])
    g4, g1 = float(u4["grad_norm"]), float(u1["grad_norm"])
    _require(_finite([float(m4["rl_loss"]), m4["reward_mean"], l4, l1, g4,
                      g1]), "cycle reward/loss/grad-norm finite")
    _require(_close(l4, l1, floor=1e-3),
             f"mesh and one-device rl_loss agree ({l4} vs {l1})")
    _require(_close(g4, g1),
             f"mesh and one-device grad norms agree ({g4} vs {g1})")
    _require(gap["param_max_abs_diff"] <= 2e-2 * gap["param_max_abs"],
             f"updated params agree ({gap})")
    s = _spread(samples)
    _require(s["devices"] == len(jax.devices()),
             f"rollouts come back sharded over all chips ({s})")
    del state4
    return {
        "cycle_reward_mean": round(m4["reward_mean"], 4),
        "cycle_rl_loss": float(m4["rl_loss"]),
        "rl_loss_mesh_vs_one": [l4, l1],
        "grad_norm_mesh_vs_one": [g4, g1], **gap, "samples": s,
        "check": "sharded decode -> host reward -> sharded update runs; the "
                 "sharded update == the one-device update on the same "
                 "rollouts",
    }


MESH_PHASES = (("mesh_xe", phase_mesh_xe), ("mesh_scst", phase_mesh_scst))


# ---- entry ------------------------------------------------------------------


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run(scale: Scale, seed: int, chips: int, work: str) -> bool:
    """All phases of one mode; True when every phase passed."""
    from cst_captioning_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    smoke = Smoke(scale, seed, work)
    print(json.dumps({"compile_cache": cache_dir, "seed": seed,
                      "chips": chips}), flush=True)
    smoke.run_phase("corpus", lambda s: build_corpus(s))
    for name, fn in (MESH_PHASES if chips > 1 else ONE_CHIP_PHASES):
        smoke.run_phase(name, fn)
    return not smoke.failed


def main(argv: list[str] | None = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="corpus, weights and traffic all derive from it")
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4 = ONLY the data-parallel XE/SCST steps across "
                        "four chips and their one-device comparison")
    args = p.parse_args(argv)

    device = device_record()
    ok = device["platform"] == "tpu" and device["count"] >= args.chips
    if not ok:
        print(f"chip_smoke: needs {args.chips} TPU chip(s), jax reports "
              f"{device} — not carrying on without them", file=sys.stderr)
    else:
        work = tempfile.mkdtemp(prefix="chip_smoke_")
        try:
            ok = run(PRESET, args.seed, args.chips, work)
        finally:
            shutil.rmtree(work, ignore_errors=True)
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
