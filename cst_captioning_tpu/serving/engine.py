"""CaptionService: always-on caption serving with continuous batching.

The admission/batch-former loop runs the PR 5 stride machinery as a
*service*: a fixed pool of ``capacity`` decode lanes steps S-step strides
forever, and between strides — exactly where finished-lane compaction
already re-packs columns — finished requests leave their lanes and queued
requests slot in. The stride program never learns about requests: like the
offline loop, it sees a dense active prefix (host-built permutation +
``n_active``), gathered encoder pages, and per-row noise. Continuous
batching is therefore *structurally* the offline decode with a different
column occupancy per stride, which is what makes the parity pin possible:

**Per-request determinism.** Every request decodes on its OWN RNG streams
— ``fold_in(fold_in(key(seed), k), t)`` with the request's *local* step t —
and its encoder output comes from a batched admission-group encode whose
rows it owns alone. Per-row encoder AND decode math is batch-composition
independent (each row's matmul/softmax reads only its own row) and
padding-width independent (masked memory slots contribute exact-zero
softmax weight), so a request admitted mid-flight into an arbitrary lane
emits token- and logprob-BIT-identical output to
the same clip decoded offline through ``decoding.fused.fused_decode``
(pinned by tests/test_serving.py). K sampled lanes ride along as *Noisy
Parallel Approximate Decoding* (arXiv:1605.03835): the served caption is
the best-scoring lane (greedy included), an anytime quality knob that
costs only lane width.

**Zero-sync loop discipline (GL001-clean).** All device work is dispatched
through jitted closures; every host<->device crossing is explicit — one
``jax.device_put`` batch per stride for the small host-built inputs (page
table, permutation, lens) and ONE explicit ``jax.device_get`` per stride
for the emissions the host must act on (tokens/logprobs/finished — the
admission decision and the response payload ARE host data; serving's
per-stride readback is the deliberate, amortized sync point, not an
accident). Nothing else crosses implicitly: the loop body holds under
``jax.transfer_guard("disallow")`` (tests/test_serving.py sanitize test).

**Drain.** SIGTERM, a detected peer loss (resilience/health.py), or the
seeded ``serving_preempt`` chaos fault stop the loop at the next stride
boundary: in-flight strides finish, new admissions are refused, and the
queue (pending + in-flight request payloads) plus the page-table snapshot
persist to the snapshot dir. :func:`load_snapshot` replays the drained
queue through a fresh service and — per-request determinism again — yields
bit-identical tokens (pinned by the recovery test).
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from cst_captioning_tpu.config.config import BOS_ID, EOS_ID, PAD_ID
from cst_captioning_tpu.decoding.common import (
    forbid_special,
    gumbel_step_noise,
    lane_decode_step,
    npad_best_lane_index,
    selected_logprob,
    step_outputs,
)
from cst_captioning_tpu.models.captioner import CaptionModel, EncoderOutput
from cst_captioning_tpu.parallel.compile import CompilePlan, compile_fn
from cst_captioning_tpu import obs
from cst_captioning_tpu.obs import anomaly as obs_anomaly
from cst_captioning_tpu.obs import recorder as obs_recorder
from cst_captioning_tpu.obs.flops import (
    enc_and_per_tok_flops,
    serving_bank_bytes_per_stride,
)
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.resilience.preempt import PreemptionHandler
from cst_captioning_tpu.serving.pages import (
    OutOfPages,
    PageBank,
    gather_bank,
)


@dataclass(frozen=True)
class ClipRequest:
    """One caption request: unbatched features ``[F, D]`` per modality,
    per-frame masks ``[F]``, and the request's OWN rng seed (the whole
    decode is a deterministic function of this payload — replay = rerun)."""

    req_id: str
    feats: dict[str, np.ndarray]
    masks: dict[str, np.ndarray]
    seed: int = 0
    arrival_s: float = 0.0

    @property
    def num_frames(self) -> int:
        return int(next(iter(self.feats.values())).shape[0])


@dataclass
class CaptionResult:
    req_id: str
    tokens: np.ndarray        # [1+K, T] int32 — lane 0 greedy, like fused.py
    logprobs: np.ndarray      # [1+K, T] f32 untempered model logprobs
    best_lane: int            # NPAD pick: argmax sum-logprob over lanes
    caption_ids: list[int]    # best lane up to (excluding) EOS
    caption: str | None       # detokenized when the service has a vocab
    latency_s: float          # arrival -> completion (queue wait included)
    phases: dict[str, float]  # queue_wait / encode / decode / detok seconds
    param_version: int = 0    # admission-pinned version this decode ran under


@dataclass
class ServeReport:
    results: dict[str, CaptionResult] = field(default_factory=dict)
    drained: bool = False
    drain_reason: str = ""
    snapshot_dir: str | None = None
    wall_s: float = 0.0
    submitted: int = 0
    completed: int = 0
    strides: int = 0


@dataclass
class _Ticket:
    req: ClipRequest
    slot: int = -1
    t: int = 0                      # local decode step (host mirror)
    tok: np.ndarray | None = None   # [G, T] accumulation buffers
    lp: np.ndarray | None = None
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_encoded: float = 0.0
    # the param version active at admission: every stride of this request
    # decodes under THIS version's params even after a hot swap (per-lane
    # version pinning — the request is bit-identical to its offline decode
    # under the admission version). Staged (encoded, lane-less) requests
    # pin at ENCODE time: the encoder already ran under that version.
    param_version: int = 0
    # encode-ahead staging: the encoder carry parked on device until a
    # lane frees (tiny: L x 2 x [1, H] leaves); dropped at lane bind
    enc_carry: object = None


class SloMonitor:
    """Rolling-window SLO attainment + multi-window burn-rate alerting.

    One completion at a time: ``observe(latency_s, now)`` marks the request
    ok iff ``latency_s <= target_s``, then for every rolling window (default
    1-min fast / 10-min slow) computes

    - attainment  = ok / total over the window,
    - burn rate   = (1 - attainment) / (1 - objective) — how many times
      faster than sustainable the error budget is burning (1.0 = exactly
      on budget, 14.4 = a 30-day budget gone in ~2 days),

    published as ``serving.slo.attainment.<w>s`` / ``serving.slo.burn_rate.
    <w>s`` gauges. An alert trips only when the FAST window burns above
    ``fast_burn`` AND the SLOW window above ``slow_burn`` (the classic
    multi-window rule: the slow window filters blips, the fast window makes
    the page recent) — edge-triggered into the ``serving.slo.alerts``
    counter and the shared ``obs.anomaly.slo_burn`` spelling
    (obs/anomaly.py), so the serving report and the training postmortem
    timeline name SLO pain the same way. ``now`` comes from the service's
    injectable clock: tests drive the windows with a fake clock."""

    def __init__(
        self,
        target_s: float,
        objective: float = 0.99,
        windows: tuple[float, float] = (60.0, 600.0),
        fast_burn: float = 14.4,
        slow_burn: float = 6.0,
    ):
        if target_s <= 0:
            raise ValueError(f"slo target_s {target_s} must be > 0")
        if not 0.0 < objective < 1.0:
            raise ValueError(f"slo objective {objective} must be in (0, 1)")
        if len(windows) != 2 or windows[0] >= windows[1]:
            raise ValueError(
                f"slo windows {windows} must be (fast, slow) with fast < slow"
            )
        self.target_s = float(target_s)
        self.objective = float(objective)
        self.windows = tuple(float(w) for w in windows)
        self.fast_burn = float(fast_burn)
        self.slow_burn = float(slow_burn)
        self._samples: dict[float, deque] = {
            w: deque() for w in self.windows
        }
        self._alerting = False
        self.alerts = 0

    def burn_rate(self, window: float, now: float) -> float:
        """Current burn rate over ``window`` (0.0 when no samples)."""
        dq = self._samples[window]
        while dq and dq[0][0] < now - window:
            dq.popleft()
        if not dq:
            return 0.0
        att = sum(ok for _, ok in dq) / len(dq)
        return (1.0 - att) / (1.0 - self.objective)

    def observe(self, latency_s: float, now: float) -> None:
        ok = latency_s <= self.target_s
        if not ok:
            obs.counter("serving.slo.breaches").inc()
        burns = {}
        for w in self.windows:
            dq = self._samples[w]
            dq.append((now, ok))
            while dq and dq[0][0] < now - w:
                dq.popleft()
            att = sum(o for _, o in dq) / len(dq)
            burns[w] = (1.0 - att) / (1.0 - self.objective)
            obs.gauge(f"serving.slo.attainment.{int(w)}s").set(att)
            obs.gauge(f"serving.slo.burn_rate.{int(w)}s").set(burns[w])
        fast, slow = self.windows
        firing = burns[fast] >= self.fast_burn and burns[slow] >= self.slow_burn
        if firing and not self._alerting:
            # edge-triggered: one alert per excursion, not one per request
            self.alerts += 1
            obs.counter("serving.slo.alerts").inc()
            obs_anomaly.record_anomaly(
                "slo_burn",
                target_s=self.target_s,
                fast_burn=burns[fast],
                slow_burn=burns[slow],
            )
        self._alerting = firing


# the active service (drain target of the serving_preempt chaos fault and
# the module-level request_drain() entry point)
_ACTIVE: "CaptionService | None" = None
_ACTIVE_LOCK = threading.Lock()


def request_drain(reason: str = "requested") -> None:
    """Ask the active service to drain (chaos ``serving_preempt`` hook)."""
    with _ACTIVE_LOCK:
        svc = _ACTIVE
    if svc is None:
        raise RuntimeError(
            "serving_preempt fired with no active CaptionService — the "
            "fault models a preemption of the serving loop"
        )
    svc.drain(reason)


class CaptionService:
    """Continuous-batching caption service over one model + params.

    ``capacity`` decode lanes, ``num_rollouts`` K sampled lanes per request
    (lane 0 is always the greedy lane), ``stride`` steps per dispatched
    chunk (defaults to ``model.cfg.decode_stride``). The paged encoder bank
    holds ``num_pages`` pages of ``page_size`` memory slots; admission
    backpressures on page exhaustion. ``frame_bucket`` pads each clip's
    frame axis up to the next bucket multiple (<= ``cfg.max_frames``) so
    ragged clips hold fewer pages — decode output is padding-width
    invariant (module docstring), so the bucket is a pure memory knob.
    """

    def __init__(
        self,
        model: CaptionModel,
        params,
        vocab=None,
        *,
        capacity: int = 8,
        num_rollouts: int = 2,
        temperature: float = 1.0,
        max_len: int | None = None,
        min_len: int = 0,
        stride: int | None = None,
        page_size: int | None = None,
        num_pages: int | None = None,
        frame_bucket: int | None = None,
        kernel_block_b: int = 1,
        admit_group: int = 1,
        paged: bool | None = None,
        clock: Callable[[], float] = time.monotonic,
        slo_target_s: float = 0.0,
        slo_objective: float = 0.99,
        slo_fast_burn: float = 14.4,
        slo_slow_burn: float = 6.0,
        feedback: Callable[[ClipRequest, CaptionResult, int], None] | None = None,
    ):
        cfg = model.cfg
        if cfg.decoder != "lstm":
            # the paged bank holds the LSTM's encoder memory and the lane
            # state is its (c, h): a growing attention cache has no pages here
            raise NotImplementedError(
                f"CaptionService serves decoder='lstm' only (got "
                f"{cfg.decoder!r}); evaluate such a model through "
                "eval.Evaluator / cli.eval"
            )
        self.model = model
        self.params = params
        self.vocab = vocab
        self.B = int(capacity)
        self.K = int(num_rollouts)
        self.G = 1 + self.K
        self.T = int(max_len or cfg.max_len)
        self.temperature = float(temperature)
        self.min_len = int(min_len)
        self.S = max(1, min(
            int(stride if stride is not None
                else getattr(cfg, "decode_stride", 8)),
            self.T,
        ))
        self.use_kernel = getattr(cfg, "decode_impl", "xla") == "pallas"
        if self.use_kernel and self.min_len > 0:
            raise ValueError(
                "decode_impl='pallas' serving does not support min_len > 0 "
                "(the stride kernel's min-len mask is stride-global, not "
                "per-row) — use the XLA decode path"
            )
        if self.use_kernel and self.K < 1:
            raise ValueError(
                "decode_impl='pallas' serving needs num_rollouts >= 1 "
                "(the stride kernel requires the (1+K)-lane layout)"
            )
        if self.B < 1:
            raise ValueError(f"capacity {capacity} must be >= 1")
        self.n_mod = len(cfg.modalities)
        self.frame_bucket = int(frame_bucket or cfg.max_frames)
        if not (1 <= self.frame_bucket <= cfg.max_frames):
            raise ValueError(
                f"frame_bucket {self.frame_bucket} must be in "
                f"[1, max_frames={cfg.max_frames}]"
            )
        m_max = self.n_mod * cfg.max_frames
        page = int(page_size or max(self.n_mod * self.frame_bucket, 1))
        pages_per_row = -(-m_max // page)
        if num_pages is None:
            # default pool: every lane can hold a max-length clip (the
            # padded-slab equivalent); size it DOWN to see backpressure
            num_pages = self.B * pages_per_row
        # paged in-kernel attention (default wherever the stride kernel
        # runs): the stride reads pages straight from the pool by table
        # lookup — no dense [B, W, E] bank per stride, and the pool may
        # exceed one batch's dense footprint (encode-ahead staging below
        # fills the surplus). paged=False forces the dense-gather path
        # (the XLA decode always gathers).
        self.paged = self.use_kernel if paged is None else bool(paged)
        if self.paged and not self.use_kernel:
            raise ValueError(
                "paged=True needs decode_impl='pallas' — the XLA decode "
                "path has no in-kernel page reader (it runs the "
                "gather_bank fallback); leave paged unset or False"
            )
        if not self.paged and int(num_pages) > self.B * pages_per_row:
            raise ValueError(
                f"num_pages {num_pages} exceeds one batch's dense-bank "
                f"footprint ({self.B} lanes x {pages_per_row} pages) — "
                "the dense-gather path re-materializes every lane's full "
                "window per stride, so surplus pages can never be "
                "admitted; use decode_impl='pallas' with paged=True "
                "(the in-kernel page reader) to grow the pool past it"
            )
        self.bank = PageBank(num_pages, page)
        self.table_width = pages_per_row
        self.W = pages_per_row * page     # gathered memory width per row
        # device-resident per-lane page table: bound/cleared at admission
        # and completion, consumed directly by every stride dispatch
        self.bank.init_rows(self.B, self.table_width)

        # admission-group encode width. 1 (default) = one encoder pass per
        # request, which is what makes a served request bit-identical to
        # its offline B=1 decode at EVERY dtype. >1 batches same-bucket
        # admission encodes into one pass (less admission wall under
        # arrival waves) — bit-exact where the encoder gemm is row-stable
        # (f32, pinned by test). bf16 encoder gemms are batch-shape
        # sensitive, so at any non-f32 model dtype a requested group width
        # > 1 FALLS BACK to per-request encode until a bf16 row-stability
        # story exists (bench_serving ledgers the measured grouped-vs-solo
        # bf16 drift behind the documented promotion gate)
        self.requested_admit_group = max(int(admit_group), 1)
        self.admit_group = self.requested_admit_group
        if (self.admit_group > 1
                and str(getattr(cfg, "dtype", "float32")) != "float32"):
            self.admit_group = 1
            obs.counter("serving.admit_group_bf16_fallback").inc()
            obs.event(
                "serving_admit_group_fallback",
                requested=self.requested_admit_group,
                dtype=str(getattr(cfg, "dtype", "float32")),
            )
        # kernel batch-block width. 1 (default) = every lane is its own
        # block: the kernel's block-granular skips become PER-ROW skips
        # (finished rows and the compaction prefix die row by row), and each
        # row computes in exactly the [1, ..] block shape an offline B=1
        # decode uses — which is what makes serving-pallas bit-identical to
        # offline-pallas per request in interpret mode (pinned by test). On
        # the chip the kernel runs the smallest block >= this one that
        # Mosaic's tiling accepts (ops/decode_pallas._batch_block: whole
        # 8-row sublane tiles), so skips are per 8 lanes there and the
        # served-equals-offline contract is what chip_smoke.py reports
        self.kernel_block_b = int(kernel_block_b)
        self._queue: deque[ClipRequest] = deque()
        self._tickets: dict[str, _Ticket] = {}
        self._inflight: dict[int, _Ticket] = {}   # slot -> ticket
        # encode-ahead staging (paged only): requests encoded and paged in
        # while every lane is busy — they bind a lane with NO encoder pass
        # the moment one frees. This is what makes a pool larger than one
        # batch's dense footprint USEFUL: staged pages are bounded by the
        # pool, not by lane count. FIFO order: staged requests came off
        # the queue front, and bind before any new admission.
        self._staged: deque[str] = deque()
        self._free_slots: deque[int] = deque(range(self.B))
        self._state = None                        # lazy device lane state
        self._drain = threading.Event()
        self._drain_reason = ""
        self.clock = clock
        self._encode_fns: dict[int, Callable] = {}
        self._admit_fn = None
        self._stride_fn = self._build_stride_fn()
        # seed -> raw key data, jitted: `jax.random.key(seed)` EAGER would
        # stage the seed scalar implicitly (the transfer-guard test's whole
        # point); inside jit the seed arrives as an explicit device_put arg
        self._key_fn = compile_fn(
            lambda s: jax.random.key_data(jax.random.key(s)), CompilePlan()
        )
        # SLO burn-rate monitor (SloMonitor docstring): off until a target
        # exists (slo_target_s=0.0 default, or set_slo after calibration)
        self._slo_kw = dict(
            objective=slo_objective,
            fast_burn=slo_fast_burn,
            slow_burn=slo_slow_burn,
        )
        self._slo: SloMonitor | None = (
            SloMonitor(slo_target_s, **self._slo_kw)
            if slo_target_s > 0 else None
        )
        # ---- drain-free hot param swap state (README "Online RL from
        # served traffic"). The ACTIVE version admits new requests; every
        # in-flight request decodes under its admission-pinned version's
        # params, kept in _old_params until its last lane completes. A
        # publish is STAGED here and applied only at a stride boundary
        # (_apply_pending_swap) — never mid-stride, never torn.
        self.param_version = 0
        self._old_params: dict[int, object] = {}
        self._pending_publish: tuple[int, object] | None = None
        self._swap_history: list[dict] = []
        # serving-as-actor capture: called per completed request with
        # (req, result, admission param version) — tok/lp are already host
        # arrays at completion, so the capture is zero extra dispatch
        self._feedback = feedback
        obs.gauge("serving.param_version").set(0.0)
        # analytic per-token / encode FLOPs for the obs MFU counters
        feat_dims = tuple(d for _, d in cfg.modalities)
        self._enc_flops, self._tok_flops = enc_and_per_tok_flops(
            cfg.max_frames, cfg.d_embed, cfg.d_hidden, cfg.d_att,
            cfg.vocab_size, feat_dims, cfg.num_layers,
        )

    # ---- public API ---------------------------------------------------------

    def submit(self, req: ClipRequest) -> None:
        if req.req_id in self._tickets:
            raise ValueError(f"duplicate req_id {req.req_id!r}")
        if req.num_frames < 1 or req.num_frames > self.model.cfg.max_frames:
            raise ValueError(
                f"request {req.req_id!r} has {req.num_frames} frames "
                f"(need 1..{self.model.cfg.max_frames})"
            )
        if not 0 <= req.seed < 2**31:
            # the seed travels as an int32 scalar; out-of-range values
            # would silently change the request's RNG streams vs the
            # offline `jax.random.key(seed)` spelling
            raise ValueError(
                f"request {req.req_id!r} seed {req.seed} outside [0, 2^31)"
            )
        self._tickets[req.req_id] = _Ticket(req=req)
        self._queue.append(req)
        obs.counter("serving.requests_submitted").inc()

    def drain(self, reason: str = "requested") -> None:
        """Stop at the next stride boundary: finish in-flight strides,
        refuse new admissions, snapshot the queue (thread/signal-safe)."""
        self._drain_reason = self._drain_reason or reason
        self._drain.set()

    @property
    def draining(self) -> bool:
        return self._drain.is_set()

    def grow_capacity(self, new_capacity: int) -> None:
        """Grow the lane pool at a stride seam (the elastic regrow
        direction: a rejoined node re-admits a drained shard's work at
        full width). Call between :meth:`serve` calls — never mid-stride.

        Only grows: the free-slot list gains the new lane ids, the page
        bank grows proportionally (``table_width`` pages per new lane, the
        same per-lane share the constructor defaults to), the stride
        closure rebuilds for the new ``B``, and — when lane state already
        exists — every state leaf pads along the lane axis with lanes born
        FINISHED and empty, exactly like :meth:`_ensure_state` births
        them. Existing lanes' slots, pages, and in-flight decodes are
        untouched, so growing mid-service never perturbs a running
        request's stream. Shrinking is drain-and-rebuild, never in place.
        """
        new_b = int(new_capacity)
        if new_b < self.B:
            raise ValueError(
                f"grow_capacity({new_capacity}) below current capacity "
                f"{self.B} — the lane pool only grows (shrink = drain and "
                "rebuild)"
            )
        if new_b == self.B:
            return
        old_b = self.B
        grown = new_b - old_b
        self.B = new_b
        self._free_slots.extend(range(old_b, new_b))
        self.bank.grow(self.bank.num_pages + grown * self.table_width)
        self.bank.grow_rows(new_b)
        self._stride_fn = self._build_stride_fn()
        if self._state is not None:
            carry, token, finished, t_local, keys = self._state

            def pad(x, fill, axis):
                widths = [(0, 0)] * x.ndim
                widths[axis] = (0, grown)
                return jnp.pad(x, widths, constant_values=fill)

            self._state = (
                tuple((pad(c, 0, 1), pad(h, 0, 1)) for c, h in carry),
                pad(token, BOS_ID, 1),
                pad(finished, True, 1),   # new lanes are born finished
                pad(t_local, 0, 0),
                pad(keys, 0, 0),
            )
        obs.counter("serving.lanes_regrown").inc(grown)
        obs.event("serving_regrow", capacity=new_b, grown=grown)

    def set_slo(self, target_s: float) -> None:
        """(Re)arm the SLO monitor with a latency target, e.g. one
        calibrated from solo-request latency. Window
        history restarts; ``target_s <= 0`` disarms."""
        self._slo = (
            SloMonitor(target_s, **self._slo_kw) if target_s > 0 else None
        )
        if self._slo is not None:
            obs.gauge("serving.slo.target_s").set(float(target_s))

    def slo_snapshot(self) -> dict | None:
        """Current SLO-monitor state for reports (``None`` when disarmed):
        target, objective, and per-window burn rate as of now."""
        mon = self._slo
        if mon is None:
            return None
        now = self.clock()
        return {
            "target_s": mon.target_s,
            "objective": mon.objective,
            "burn_rate": {
                f"{int(w)}s": round(mon.burn_rate(w, now), 4)
                for w in mon.windows
            },
            "breach_alerts": mon.alerts,
            "param_version": self.param_version,
        }

    # ---- drain-free hot param swap ------------------------------------------

    def publish_params(self, params, version: int | None = None) -> bool:
        """Stage a new param tree for a drain-free hot swap into the live
        service. The swap applies at the NEXT stride boundary
        (:meth:`_apply_pending_swap`) — in-flight requests keep decoding
        under their admission-pinned version, new admissions pick up the
        published one; nothing drains, nothing tears.

        Version-gated: ``version`` (default: one past the newest known)
        must be strictly newer than both the active version and any
        still-pending publish — a stale or duplicate publish (e.g. one
        replayed after a preemption) is REFUSED, counted, and returns
        False. A newer publish supersedes a pending unapplied one."""
        floor = self.param_version
        if self._pending_publish is not None:
            floor = max(floor, self._pending_publish[0])
        version = floor + 1 if version is None else int(version)
        if version <= floor:
            obs.counter("serving.param_swaps_refused").inc()
            obs.event(
                "serving_param_swap_refused", version=version,
                active=self.param_version, reason="stale_version",
            )
            return False
        self._pending_publish = (version, params)
        obs.event("serving_param_publish", version=version)
        return True

    def _apply_pending_swap(self) -> bool:
        """Apply a staged publish at the stride boundary — the ONLY place
        the active version ever changes, so a swap is atomic with respect
        to strides: every stride runs entirely under whole versions.

        The ``serving.param_swap`` chaos seam fires BEFORE any state
        mutates: a preemption landing exactly mid-swap requests a drain,
        the check below refuses the swap, and the drained snapshot replays
        entirely under the OLD version — the swap is fully applied or
        fully refused, never torn."""
        if self._pending_publish is None:
            return False
        version, params = self._pending_publish
        chaos.visit("serving.param_swap")
        if self.draining:
            self._pending_publish = None
            obs.counter("serving.param_swaps_refused").inc()
            obs.event(
                "serving_param_swap_refused", version=version,
                active=self.param_version, reason="draining",
            )
            return False
        self._pending_publish = None
        prev = self.param_version
        if self._inflight or self._staged:
            # in-flight lanes AND staged (encoded, lane-less) requests pin
            # the outgoing version until they complete
            self._old_params[prev] = self.params
        self.params = params
        self.param_version = version
        self._swap_history.append({
            "version": version, "from": prev,
            "inflight_pinned": len(self._inflight),
        })
        obs.counter("serving.param_swaps").inc()
        obs.gauge("serving.param_version").set(float(version))
        obs.event(
            "serving_param_swap", version=version, prev=prev,
            inflight_pinned=len(self._inflight),
        )
        return True

    def _params_for(self, version: int):
        """The param tree a stride for ``version``-pinned lanes decodes
        under: the live tree for the active version, else the retained
        tree the swap parked for still-in-flight lanes."""
        if version == self.param_version:
            return self.params
        return self._old_params[version]

    def _retire_versions(self) -> None:
        """Drop retained old-param trees no in-flight lane pins anymore
        (called after completions, so a swap's old version lives exactly
        as long as its last admitted request)."""
        if not self._old_params:
            return
        live = {t.param_version for t in self._inflight.values()}
        live |= {
            self._tickets[r].param_version
            for r in self._staged if r in self._tickets
        }
        for v in [v for v in self._old_params if v not in live]:
            del self._old_params[v]
            obs.counter("serving.param_versions_retired").inc()

    def serve(
        self,
        requests: Iterable[ClipRequest] = (),
        *,
        snapshot_dir: str | None = None,
        realtime: bool = False,
        idle_wait_s: float = 0.002,
    ) -> ServeReport:
        """Run the admission/decode loop until the queue drains (or a drain
        is requested). ``realtime=True`` honors each request's ``arrival_s``
        against the wall clock (open-loop traffic); otherwise every
        submitted request is immediately admissible."""
        global _ACTIVE
        for req in sorted(requests, key=lambda r: r.arrival_s):
            self.submit(req)
        report = ServeReport(submitted=len(self._tickets))
        t0 = self.clock()
        now = lambda: self.clock() - t0  # noqa: E731
        with _ACTIVE_LOCK:
            prev_active = _ACTIVE
            _ACTIVE = self
        pre = PreemptionHandler().install()
        try:
            while True:
                chaos.visit("serving.step")
                if pre.requested:
                    self.drain("sigterm")
                mon = _health_monitor()
                if mon is not None and mon.peer_lost:
                    self.drain("peer_loss")
                if self.draining:
                    # stride-boundary drain: the dispatched stride already
                    # finished (we only reach here between strides); both
                    # in-flight AND pending requests persist to the
                    # snapshot and replay from scratch bit-identically
                    break
                # stride-boundary hot swap: a staged publish lands here,
                # BEFORE admission, so every request admitted this
                # iteration pins the post-swap version
                self._apply_pending_swap()
                if self.draining:
                    continue  # a swap-seam preempt: drain at the loop top
                self._admit_arrived(now, realtime)
                if not self._inflight:
                    if not self._queue:
                        break
                    # queued work not yet arrived (realtime) or blocked on
                    # pages freed only by completions that cannot come —
                    # the former waits, the latter is a sizing error
                    if not realtime:
                        raise OutOfPages(
                            "queue is non-empty but nothing can be "
                            "admitted: a single request needs more pages "
                            "than the whole pool"
                        )
                    time.sleep(idle_wait_s)
                    continue
                self._run_stride(report, now)
            report.drained = self.draining
            report.drain_reason = self._drain_reason
            if self.draining:
                report.snapshot_dir = self._write_snapshot(snapshot_dir)
                obs.event(
                    "serving_drain", reason=self._drain_reason,
                    pending=len(self._queue), inflight=len(self._inflight),
                    snapshot=report.snapshot_dir,
                )
                obs.counter("serving.drains").inc()
                # postmortem bundle BEFORE the working set is released: the
                # pending/inflight census below is still live evidence
                self._drain_postmortem(report)
                # release the drained working set AFTER the snapshot
                # captured the page table (the object stays reusable)
                for slot in sorted(self._inflight):
                    ticket = self._inflight.pop(slot)
                    self.bank.free(ticket.req.req_id)
                    self._free_slots.append(slot)
                    self._tickets.pop(ticket.req.req_id, None)
                for rid in self._staged:
                    self.bank.free(rid)
                    self._tickets.pop(rid, None)
                self._staged.clear()
                for req in self._queue:
                    self._tickets.pop(req.req_id, None)
                self._queue.clear()
        finally:
            pre.uninstall()
            with _ACTIVE_LOCK:
                _ACTIVE = prev_active
        report.wall_s = now()
        report.completed = len(report.results)
        return report

    def _drain_postmortem(self, report: ServeReport) -> None:
        """A drained service leaves the same forensic a dying trainer does:
        a flight-recorder postmortem bundle whose registry carries the SLO
        snapshot, so ``cli.obs_report --postmortem`` diagnoses a SIGTERM /
        peer-loss / chaos drain with the training tooling. Dumps through the
        process-global recorder when one is configured (serving inside a
        training run); otherwise an ephemeral recorder dropping the bundle
        next to the obs event stream, or into the drain snapshot as a last
        resort. Best-effort — a failed dump must never break the drain."""
        extra = {
            "serving": {
                "drain_reason": self._drain_reason,
                "pending": len(self._queue),
                "inflight": len(self._inflight),
                "slo": self.slo_snapshot(),
                # param-version attribution: which version was serving at
                # the drain, and the recent swap arcs — the fleet merge
                # (obs/fleet.py) pins a reward/SLO regression to these
                "param_version": self.param_version,
                "param_swaps": len(self._swap_history),
                "swap_history": self._swap_history[-8:],
            }
        }
        fields = dict(
            drain_reason=self._drain_reason,
            pending=len(self._queue),
            inflight=len(self._inflight),
        )
        reason = f"serving_drain_{self._drain_reason or 'request'}"
        try:
            fr = obs_recorder.active()
            if fr is not None:
                fr.postmortem(reason, registry_extra=extra, **fields)
                return
            span_rec = obs.active()
            out_dir = (
                span_rec.out_dir if span_rec is not None
                else report.snapshot_dir
            )
            if not out_dir:
                return  # no obs, no snapshot: nowhere durable to dump
            fr = obs_recorder.FlightRecorder(
                1, out_dir, run="serving", max_dumps=1
            )
            try:
                fr.postmortem(reason, registry_extra=extra, **fields)
            finally:
                fr.close()
        except Exception:
            # counted, not raised: drains run on the unwind path
            obs.counter("serving.drain_postmortem_error").inc()

    def stride_program_text(self) -> str | None:
        """Compiled text of the stride program as the backend built it —
        what a caller reads to verify which kernels the program REALLY
        holds (a ``tpu_custom_call`` is a Mosaic kernel; a flag is a wish).
        Available once the service has admitted at least one request (the
        pools and lane state exist then)."""
        args = self._stride_args()
        if args is None:
            return None
        return self._stride_fn.lower(*args).compile().as_text()

    def _stride_args(self):
        """Example arguments of one stride dispatch (identity permutation,
        every lane stepping), or None before the first admission."""
        if self._state is None or self.bank.mem is None:
            return None
        B = self.B
        perm = np.arange(B, dtype=np.int32)
        return (
            self.params,
            (self.bank.mem, self.bank.proj, self.bank.mask),
            self.bank.row_table, self.bank.row_lens,
            perm, perm, np.int32(B), self._state,
            np.ones((B,), bool),
        )

    # ---- admission ----------------------------------------------------------

    def _admit_arrived(self, now, realtime: bool) -> None:
        # staged requests bind freed lanes FIRST (they left the queue front
        # earlier, so FIFO holds) — binding is encode-free: the pages and
        # the parked encoder carry already exist
        while self._staged and self._free_slots:
            rid = self._staged.popleft()
            ticket = self._tickets[rid]
            with obs.span("serving.bind_staged", req=rid):
                self._bind_lane(ticket)
            obs.counter("serving.staged_bound").inc()
        # collect every currently-admissible request (a free lane AND
        # enough free pages), grouped by frame bucket — each group encodes
        # as ONE batched pass. Per-row encoder math is batch-composition
        # independent (module docstring), so batching the admission encode
        # changes no bits, only the wall clock a serialized-B=1 admission
        # loop would burn (the static policy amortizes its encoder over the
        # batch; the continuous former must too, or it spots the comparison
        # an encoder pass per request)
        groups: dict[int, list[ClipRequest]] = {}
        free = len(self._free_slots)
        reserved = 0
        while self._queue and free:
            req = self._queue[0]
            if realtime and req.arrival_s > now():
                break
            n_pages = self.bank.pages_for(self.n_mod * self._padded_frames(req))
            if self.bank.free_pages - reserved < n_pages:
                obs.counter("serving.admission_blocked_pages").inc()
                break
            self._queue.popleft()
            groups.setdefault(self._padded_frames(req), []).append(req)
            reserved += n_pages
            free -= 1
        for F, reqs in groups.items():
            for i in range(0, len(reqs), self.admit_group):
                chunk = reqs[i:i + self.admit_group]
                with obs.span("serving.admit", requests=len(chunk)):
                    self._admit_group(F, chunk, now)
        # encode-ahead staging (paged path only): every lane is busy but
        # pages are free — encode queue-front requests NOW and park their
        # pages, so (a) a freed lane binds with zero encode on its critical
        # path and (b) the pool's surplus past one batch's dense footprint
        # actually fills. The dense-gather path cannot do this: its pool is
        # constructor-capped at the dense footprint.
        sgroups: dict[int, list[ClipRequest]] = {}
        if self.paged and not self._free_slots:
            reserved = 0
            while self._queue:
                req = self._queue[0]
                if realtime and req.arrival_s > now():
                    break
                n_pages = self.bank.pages_for(
                    self.n_mod * self._padded_frames(req)
                )
                if self.bank.free_pages - reserved < n_pages:
                    break
                self._queue.popleft()
                sgroups.setdefault(self._padded_frames(req), []).append(req)
                reserved += n_pages
            for F, reqs in sgroups.items():
                for i in range(0, len(reqs), self.admit_group):
                    chunk = reqs[i:i + self.admit_group]
                    with obs.span("serving.stage", requests=len(chunk)):
                        self._admit_group(F, chunk, now, stage=True)
            obs.gauge("serving.staged").set(len(self._staged))
        if groups or sgroups or self._queue:
            obs.gauge("serving.queue_depth").set(len(self._queue))

    def _padded_frames(self, req: ClipRequest) -> int:
        b = self.frame_bucket
        return min(-(-req.num_frames // b) * b, self.model.cfg.max_frames)

    def _admit_group(self, F: int, reqs: list[ClipRequest], now,
                     stage: bool = False) -> None:
        t_admit = now()
        t_enc0 = time.perf_counter()
        with obs.span("serving.encode", requests=len(reqs)):
            enc = self._encode_batch(reqs, F)
        enc_s = (time.perf_counter() - t_enc0) / len(reqs)
        m_len = self.n_mod * F
        for i, req in enumerate(reqs):
            ticket = self._tickets[req.req_id]
            ticket.t_submit = ticket.t_submit or req.arrival_s
            ticket.t_admit = t_admit
            ticket.param_version = self.param_version
            enc_i = jax.tree.map(lambda x: x[i:i + 1], enc)
            pages = self.bank.alloc(req.req_id, m_len)
            self.bank.store(
                pages, enc_i.memory, enc_i.memory_proj, enc_i.memory_mask
            )
            ticket.t_encoded = now()
            ticket.enc_carry = enc_i.carry
            self._ensure_state(enc_i.carry)
            if stage:
                self._staged.append(req.req_id)
                obs.counter("serving.requests_staged").inc()
            else:
                self._bind_lane(ticket)
            obs.counter("serving.requests_admitted").inc()
            obs.counter("flops.serving.encode").inc(self._enc_flops)
            obs.histogram("serving.queue_wait_seconds").observe(
                max(ticket.t_admit - ticket.t_submit, 0.0)
            )
            obs.histogram("serving.encode_seconds").observe(enc_s)
        obs.gauge("serving.slots_in_use").set(len(self._inflight))
        obs.gauge("serving.pages_in_use").set(self.bank.pages_in_use)

    def _bind_lane(self, ticket: _Ticket) -> None:
        """Bind an encoded request (fresh or staged) to a free lane: set
        the device page-table row, seed the lane state from the parked
        encoder carry, arm the request's own RNG stream. No encoder work —
        the encode happened at admission/staging time."""
        slot = self._free_slots.popleft()
        ticket.slot = slot
        ticket.tok = np.full((self.G, self.T), PAD_ID, np.int32)
        ticket.lp = np.zeros((self.G, self.T), np.float32)
        self._inflight[slot] = ticket
        self.bank.bind_row(slot, ticket.req.req_id)
        key_raw = self._key_fn(jax.device_put(np.int32(ticket.req.seed)))
        self._state = self._admit_fn(
            self._state, jax.device_put(np.int32(slot)), ticket.enc_carry,
            key_raw,
        )
        ticket.enc_carry = None  # the lane state owns the carry now

    def _encode_batch(self, reqs: list[ClipRequest], F: int) -> EncoderOutput:
        """One batched encoder pass for an admission group. The batch dim
        pads to the next power of two (repeating row 0; surplus rows are
        discarded) so compile count stays O(log capacity) per frame bucket
        instead of one program per group size."""
        n = len(reqs)
        npad = 1
        while npad < n:
            npad *= 2
        fn = self._encode_fns.get((F, npad))
        if fn is None:
            model = self.model
            fn = compile_fn(
                lambda p, f, m: model.apply(
                    p, f, m, method=CaptionModel.encode
                ),
                CompilePlan(),
            )
            self._encode_fns[(F, npad)] = fn
        feats, masks = {}, {}
        for name, _ in self.model.cfg.modalities:
            rows, mrows = [], []
            for req in reqs:
                x = np.asarray(req.feats[name], np.float32)
                mk = np.asarray(req.masks[name], np.float32)
                pad = F - x.shape[0]
                rows.append(np.pad(x, ((0, pad), (0, 0))))
                mrows.append(np.pad(mk, ((0, pad),)))
            rows += rows[:1] * (npad - n)
            mrows += mrows[:1] * (npad - n)
            feats[name] = jax.device_put(np.stack(rows))
            masks[name] = jax.device_put(np.stack(mrows))
        return fn(self.params, feats, masks)

    # ---- device lane state --------------------------------------------------

    def _ensure_state(self, enc_carry) -> None:
        if self._state is not None:
            return
        G, B = self.G, self.B
        carry = tuple(
            (
                jnp.zeros((G, B) + c.shape[1:], c.dtype),
                jnp.zeros((G, B) + h.shape[1:], h.dtype),
            )
            for c, h in enc_carry
        )
        # key-data layout probed abstractly (eval_shape: no device values,
        # no transfers — the impl-dependent raw width is all we need)
        key_aval = jax.eval_shape(
            lambda: jax.random.key_data(jax.random.key(0))
        )
        self._state = (
            carry,
            jnp.full((G, B), BOS_ID, jnp.int32),
            jnp.ones((G, B), bool),        # empty lanes are born finished
            jnp.zeros((B,), jnp.int32),
            jnp.zeros((B,) + key_aval.shape, key_aval.dtype),
        )
        def admit(state, col, enc_carry, key_raw):
            carry, token, finished, t_local, keys = state
            new_carry = tuple(
                (
                    c.at[:, col].set(
                        jnp.broadcast_to(ec[0], (G,) + ec.shape[1:])
                    ),
                    h.at[:, col].set(
                        jnp.broadcast_to(eh[0], (G,) + eh.shape[1:])
                    ),
                )
                for (c, h), (ec, eh) in zip(carry, enc_carry)
            )
            return (
                new_carry,
                token.at[:, col].set(BOS_ID),
                finished.at[:, col].set(False),
                t_local.at[col].set(0),
                keys.at[col].set(key_raw),
            )

        L = len(enc_carry)
        assert L == len(carry)
        self._admit_fn = compile_fn(
            admit, CompilePlan(donate_argnums=(0,))
        )

    # ---- the stride ---------------------------------------------------------

    def _build_stride_fn(self):
        model, params_model = self.model, None  # params passed per call
        B, G, K, S, T, W = self.B, self.G, self.K, self.S, self.T, self.W
        V = model.cfg.vocab_size
        temp, min_len = self.temperature, self.min_len
        use_kernel = self.use_kernel
        paged = self.paged
        num_layers = model.cfg.num_layers
        kernel_block_b = self.kernel_block_b

        def row_noise(key_raw, t_b):
            """[S, K, V] Gumbel noise on THIS request's offline streams:
            ``gumbel(fold_in(fold_in(key, k), t), (1, V))`` — the exact
            call shape ``fused_decode`` makes for a B=1 batch, so the bits
            match the offline decode draw for draw. Steps past T clamp to
            T-1 like ``rollout_step_keys`` (the overhang draws only ever
            feed discarded emissions)."""
            key = jax.random.wrap_key_data(key_raw)
            ks = jax.vmap(lambda k: jax.random.fold_in(key, k))(
                jnp.arange(K)
            )

            def step_noise(t):
                ks_t = jax.vmap(lambda kk: jax.random.fold_in(kk, t))(ks)
                return gumbel_step_noise(ks_t, (1, V), jnp.float32)[:, 0]

            ts = jnp.minimum(t_b + jnp.arange(S), T - 1)
            return jax.vmap(step_noise)(ts)

        def stride(params, pools, table, lens, perm, inv, n_active, state,
                   step_mask):
            """One S-step stride over the lanes ``step_mask`` selects.

            ``step_mask`` [B] (slot order) freezes the lanes it excludes:
            they are treated as finished for the decode, their state leaves
            select back to the pre-stride values, and their ``t_local``
            does not advance — so their RNG streams resume exactly where
            they paused. A hot param swap runs one stride per live version
            with that version's lanes masked in; the all-True mask is the
            single-version case and computes bit-identically to an unmasked
            stride (``where(True, new, old) == new``)."""
            carry, token, finished, t_local, keys = state
            take1 = lambda x: jnp.take(x, perm, axis=1)  # noqa: E731
            carry_c = jax.tree.map(take1, carry)
            token_c, fin_c = take1(token), take1(finished)
            mask_c = jnp.take(step_mask, perm)
            carry_c0, token_c0, fin_c0 = carry_c, token_c, fin_c
            fin_c = fin_c | ~mask_c[None, :]
            t_c = jnp.take(t_local, perm)
            keys_c = jnp.take(keys, perm, axis=0)
            # compaction permutes TABLE ROWS, never pages: the permuted
            # [B, width] table is all the decode needs on either path
            table_c = jnp.take(table, perm, axis=0)
            lens_c = jnp.take(lens, perm)
            if K:
                noise = jnp.transpose(
                    jax.vmap(row_noise)(keys_c, t_c), (1, 2, 0, 3)
                )  # [S, K, B, V]
            else:
                noise = jnp.zeros((S, 0, B, V), jnp.float32)

            if use_kernel and paged:
                from cst_captioning_tpu.ops.decode_pallas import (
                    fused_decode_stride_paged,
                )

                # pool + table pass straight through: the kernel resolves
                # pages by table lookup — no dense bank this stride
                carry_c, toks, lps = fused_decode_stride_paged(
                    params["params"]["cell"], carry_c, token_c, fin_c,
                    *pools, table_c, noise, jnp.int32(0), n_active,
                    steps=S, temperature=temp, min_len=0,
                    num_layers=num_layers, mem_lens=lens_c,
                    block_b=kernel_block_b,
                )
                fin_c = fin_c | jnp.any(toks == EOS_ID, axis=0)
                token_c = toks[-1]
            elif use_kernel:
                from cst_captioning_tpu.ops.decode_pallas import (
                    fused_decode_stride,
                )

                mem, proj, mask = gather_bank(pools, table_c)
                carry_c, toks, lps = fused_decode_stride(
                    params["params"]["cell"], carry_c, token_c, fin_c,
                    mem, proj, mask,
                    noise, jnp.int32(0), n_active, steps=S,
                    temperature=temp, min_len=0, num_layers=num_layers,
                    mem_lens=lens_c, block_b=kernel_block_b,
                )
                fin_c = fin_c | jnp.any(toks == EOS_ID, axis=0)
                token_c = toks[-1]
            else:
                mem, proj, mask = gather_bank(pools, table_c)
                enc_c = EncoderOutput(mem, proj, mask, ())
                def step(st, s):
                    carry_s, token_s, fin_s = st
                    carry_s, logits = lane_decode_step(
                        model, params, carry_s, token_s, enc_c
                    )
                    logits = forbid_special(logits)
                    if min_len > 0:
                        blocked = logits.at[..., EOS_ID].set(-1.0e9)
                        logits = jnp.where(
                            ((t_c + s) < min_len)[None, :, None],
                            blocked, logits,
                        )
                    g_nxt = jnp.argmax(logits[0], axis=-1)
                    tl = logits[1:] / temp
                    s_nxt = jnp.argmax(tl + noise[s], axis=-1)
                    nxt = jnp.concatenate(
                        [g_nxt[None], s_nxt], axis=0
                    ).astype(jnp.int32)
                    lp = selected_logprob(logits, nxt)
                    nxt, lp, fin_s = step_outputs(nxt, lp, fin_s)
                    return (carry_s, nxt, fin_s), (nxt, lp)

                (carry_c, token_c, fin_c), (toks, lps) = jax.lax.scan(
                    step, (carry_c, token_c, fin_c), jnp.arange(S)
                )

            # frozen-lane select-back: both decode paths advance carry for
            # rows they treat as finished (the scan's lane step computes
            # every column), so lanes outside the mask restore their
            # pre-stride state bit-exactly — a masked-out lane's stream is
            # untouched, not merely ignored
            def sel(new, old):
                m = mask_c.reshape((1, -1) + (1,) * (new.ndim - 2))
                return jnp.where(m, new, old)

            carry_c = jax.tree.map(sel, carry_c, carry_c0)
            token_c = sel(token_c, token_c0)
            fin_c = sel(fin_c, fin_c0)
            back1 = lambda x: jnp.take(x, inv, axis=1)  # noqa: E731
            new_state = (
                jax.tree.map(back1, carry_c),
                back1(token_c),
                back1(fin_c),
                t_local + S * step_mask.astype(jnp.int32),
                keys,
            )
            return new_state, jnp.take(toks, inv, axis=2), jnp.take(
                lps, inv, axis=2
            )

        return compile_fn(stride, CompilePlan(donate_argnums=(7,)))

    def _run_stride(self, report: ServeReport, now) -> None:
        active = sorted(self._inflight)
        perm = np.fromiter(
            (s for s in active), np.int32, len(active)
        )
        rest = np.fromiter(
            (s for s in range(self.B) if s not in self._inflight),
            np.int32, self.B - len(active),
        )
        perm = np.concatenate([perm, rest])
        inv = np.argsort(perm, kind="stable").astype(np.int32)
        # the page table and per-lane lengths are DEVICE-resident (bound at
        # lane bind, cleared at completion) — nothing per-stride to build
        # or upload for them; only the permutation/masks cross per stride
        # group active lanes by admission-pinned param version: one stride
        # dispatch per LIVE version, each under that version's params with
        # the other versions' lanes frozen (step_mask). The common single-
        # version case is exactly the old one-dispatch stride (all-True
        # mask); across a hot swap the groups share the lane state and the
        # per-lane RNG streams stay untouched, so every request remains
        # bit-identical to its offline decode under its pinned version.
        by_ver: dict[int, list[int]] = {}
        for slot in active:
            by_ver.setdefault(
                self._inflight[slot].param_version, []
            ).append(slot)
        versions = sorted(by_ver)
        if len(versions) <= 1:
            masks = [np.ones((self.B,), bool)]
        else:
            masks = []
            for v in versions:
                m = np.zeros((self.B,), bool)
                m[by_ver[v]] = True
                masks.append(m)
        with obs.span(
            "serving.stride", active=len(active), versions=len(versions)
        ):
            dev = jax.device_put(
                (perm, inv, np.int32(len(active)), tuple(masks))
            )
            perm_d, inv_d, n_d, masks_d = dev
            outs = []
            for v, mask_d in zip(versions, masks_d):
                self._state, toks, lps = self._stride_fn(
                    self._params_for(v),
                    (self.bank.mem, self.bank.proj, self.bank.mask),
                    self.bank.row_table, self.bank.row_lens,
                    perm_d, inv_d, n_d, self._state, mask_d,
                )
                outs.append((toks, lps))
            # the per-stride sync point: ONE explicit readback of the small
            # host-facing outputs (module docstring)
            outs_np, fin_np = jax.device_get(
                (tuple(outs), self._state[2])
            )
        report.strides += 1
        obs.counter("serving.strides").inc()
        obs.counter("flops.serving.stride").inc(
            len(active) * self.G * self.S * self._tok_flops
        )
        obs.gauge("serving.pages.in_use").set(self.bank.pages_in_use)
        obs.gauge("serving.pages.free").set(self.bank.free_pages)
        obs.gauge("serving.pages.table_rows").set(self.B)
        if self.paged and self.bank.mem is not None:
            # the dense-gather path would have paid 3x the bank bytes per
            # dispatch (pool read + bank write + kernel read); the paged
            # kernel pays 1x — count the 2x saved, per version dispatch
            E = int(self.bank.mem.shape[-1])
            A = int(self.bank.proj.shape[-1])
            nbytes = int(self.bank.mem.dtype.itemsize)
            dense = serving_bank_bytes_per_stride(
                self.B, self.W, E, A, nbytes, paged=False
            )
            paged = serving_bank_bytes_per_stride(
                self.B, self.W, E, A, nbytes, paged=True
            )
            obs.counter("serving.gather_bytes_avoided").inc(
                len(versions) * (dense - paged)
            )
        for v, (toks_np, lps_np) in zip(versions, outs_np):
            for slot in by_ver[v]:
                ticket = self._inflight[slot]
                n = min(self.S, self.T - ticket.t)
                ticket.tok[:, ticket.t:ticket.t + n] = toks_np[:n, :, slot].T
                ticket.lp[:, ticket.t:ticket.t + n] = lps_np[:n, :, slot].T
                ticket.t += n
                if bool(fin_np[:, slot].all()) or ticket.t >= self.T:
                    self._complete(ticket, report, now)
        self._retire_versions()

    def _complete(self, ticket: _Ticket, report: ServeReport, now) -> None:
        with obs.span("serving.detok", req=ticket.req.req_id):
            t_det0 = time.perf_counter()
            best = int(npad_best_lane_index(ticket.lp))
            row = ticket.tok[best]
            ids: list[int] = []
            for tok in row:
                tok = int(tok)
                if tok in (EOS_ID, PAD_ID):
                    break
                ids.append(tok)
            caption = self.vocab.decode(row) if self.vocab is not None else None
            detok_s = time.perf_counter() - t_det0
        t_done = now()
        self._inflight.pop(ticket.slot)
        self._free_slots.append(ticket.slot)
        self.bank.clear_row(ticket.slot)
        self.bank.free(ticket.req.req_id)
        # evict the ticket: an always-on service must not grow state per
        # served request (and a later request may legitimately reuse an id)
        self._tickets.pop(ticket.req.req_id, None)
        phases = {
            "queue_wait": max(ticket.t_admit - ticket.t_submit, 0.0),
            "encode": max(ticket.t_encoded - ticket.t_admit, 0.0),
            "decode": max(t_done - ticket.t_encoded, 0.0),
            "detok": detok_s,
        }
        latency = max(t_done - ticket.t_submit, 0.0)
        result = CaptionResult(
            req_id=ticket.req.req_id,
            tokens=ticket.tok,
            logprobs=ticket.lp,
            best_lane=best,
            caption_ids=ids,
            caption=caption,
            latency_s=latency,
            phases=phases,
            param_version=ticket.param_version,
        )
        report.results[ticket.req.req_id] = result
        obs.counter("serving.requests_completed").inc()
        obs.gauge("serving.slots_in_use").set(len(self._inflight))
        obs.gauge("serving.pages_in_use").set(self.bank.pages_in_use)
        obs.histogram("serving.decode_seconds").observe(
            phases["decode"]
        )
        obs.histogram("serving.detok_seconds").observe(detok_s)
        obs.histogram("serving.latency_seconds").observe(latency)
        if self._slo is not None:
            # t_done is the service's monotone clock (injectable): the SLO
            # windows slide on the same timeline the latencies came from
            self._slo.observe(latency, t_done)
        obs.event(
            "serving_request", req=ticket.req.req_id, latency_s=latency,
            best_lane=best, steps=ticket.t,
            param_version=ticket.param_version, **{
                f"{k}_s": v for k, v in phases.items()
            },
        )
        if self._feedback is not None:
            # serving-as-actor feedback capture: the completed request's
            # (greedy + sampled lanes, logprobs, seed, pinned version) go
            # to the online learner — tok/lp are already host arrays, so
            # this dispatches nothing on device
            self._feedback(ticket.req, result, ticket.param_version)

    # ---- drain persistence --------------------------------------------------

    def _write_snapshot(self, snapshot_dir: str | None) -> str | None:
        if snapshot_dir is None:
            return None
        os.makedirs(snapshot_dir, exist_ok=True)
        # in-flight first (they were admitted earlier), then staged (encoded
        # but not yet bound to a lane), then queue order — replay preserves
        # the service order
        drained: list[ClipRequest] = [
            self._inflight[s].req for s in sorted(
                self._inflight, key=lambda s: self._inflight[s].t_admit
            )
        ] + [
            self._tickets[r].req for r in self._staged if r in self._tickets
        ] + list(self._queue)
        arrays: dict[str, np.ndarray] = {}
        manifest = {
            "requests": [],
            "page_table": self.bank.snapshot(),
            "in_flight_steps": {
                t.req.req_id: t.t for t in self._inflight.values()
            },
            "staged": list(self._staged),
            "drain_reason": self._drain_reason,
        }
        for i, req in enumerate(drained):
            manifest["requests"].append({
                "req_id": req.req_id,
                "seed": req.seed,
                "arrival_s": req.arrival_s,
                "modalities": sorted(req.feats),
            })
            for name in req.feats:
                arrays[f"{i}.feats.{name}"] = np.asarray(
                    req.feats[name], np.float32
                )
                arrays[f"{i}.masks.{name}"] = np.asarray(
                    req.masks[name], np.float32
                )
        for req in drained:
            self._tickets.pop(req.req_id, None)
        np.savez(os.path.join(snapshot_dir, "queue.npz"), **arrays)
        tmp = os.path.join(snapshot_dir, ".manifest.json.tmp")
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump(manifest, f, indent=2)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, os.path.join(snapshot_dir, "manifest.json"))
        return snapshot_dir


def load_snapshot(
    snapshot_dir: str,
    service: "CaptionService | None" = None,
    grow_to: int | None = None,
) -> list[ClipRequest]:
    """Drained queue -> requests, in the order the service would have run
    them. Re-serving them through a fresh CaptionService yields bit-identical
    tokens (per-request determinism; in-flight requests restart from step 0).

    The regrow direction: pass ``service`` to replay the snapshot onto a
    rejoined node — the drained requests resubmit in their drain order so
    admissions resume where the outage cut them off. ``grow_to`` first
    grows the service's lane pool to that capacity at a stride seam
    (:meth:`CaptionService.grow_capacity`), covering the shard that rode
    out the outage at reduced width. The bare one-argument call keeps the
    old read-only contract and just returns the requests."""
    with open(os.path.join(snapshot_dir, "manifest.json"),
              encoding="utf-8") as f:
        manifest = json.load(f)
    data = np.load(os.path.join(snapshot_dir, "queue.npz"))
    out: list[ClipRequest] = []
    for i, rec in enumerate(manifest["requests"]):
        feats = {m: data[f"{i}.feats.{m}"] for m in rec["modalities"]}
        masks = {m: data[f"{i}.masks.{m}"] for m in rec["modalities"]}
        out.append(ClipRequest(
            req_id=rec["req_id"], feats=feats, masks=masks,
            seed=int(rec["seed"]), arrival_s=float(rec["arrival_s"]),
        ))
    if service is not None:
        if grow_to is not None:
            service.grow_capacity(grow_to)
        for req in out:
            service.submit(req)
        obs.counter("serving.requests_replayed").inc(len(out))
        obs.event(
            "serving_replay", requests=len(out), capacity=service.B,
            drain_reason=manifest.get("drain_reason", ""),
        )
    return out


def _health_monitor():
    """The active elastic-health monitor, if resilience wiring started one
    (lazy import: serving must not drag the health stack in by default)."""
    from cst_captioning_tpu.resilience import health

    return health.active_monitor()
