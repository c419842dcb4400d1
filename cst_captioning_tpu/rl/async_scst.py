"""Decoupled actor/learner SCST (Podracer/Sebulba-style, arXiv 2104.06272).

``train.rl_topology="decoupled"``: the data mesh splits into an ACTOR
submesh and a LEARNER submesh (parallel/submesh.py). Actor devices run the
fused rollout decode continuously into a device-resident double-buffered
rollout ring (:class:`RolloutRing` — tokens + sample logprobs + the
per-batch RNG stream, ``rl.rollout_depth`` batches deep); learner devices
consume completed batches with the existing in-scan-logp ``rl_update``
factories (the comms config rides along unchanged); params broadcast
actor-ward after every learner update. A rollout decoded under params more
than ``rl.staleness_bound`` learner updates old at consumption time is
DROPPED and recounted: re-decoded under the actor's refreshed params with
the entry's stored RNG key, so the drop/recount sequence is deterministic
run-to-run.

The single-controller dispatch loop is the async machinery: every decode
and update is dispatched without waiting, so with disjoint submeshes the
actor's decode of batch *i* genuinely overlaps the learner's update of
batch *i-depth+1* on different devices — the host only blocks when it
reads rollout tokens back for the consensus reward.

STRICT mode (``strict=True``, or ``rollout_depth=1`` + ``staleness_bound=0``)
pins bit-identity: both roles run on the FULL mesh (so the decode's
``axis_index`` RNG folds match the sync loop's), the ring depth replays the
sync schedule exactly — depth 2 IS the sync loop's default 1-deep pipeline
(decode(i) one update stale, update(i-1) dispatched after decode(i)), depth
1 the ``pipelined=False`` sequential loop — and the per-batch
``rng, srng = jax.random.split(rng)`` chain is the sync loop's — tokens,
logprobs, params, and opt_state reproduce ``SCSTTrainer.train_epoch``
bit-for-bit (tests/test_async_scst.py), PER EPOCH-SIZED CALL: the ring
fills and drains inside every epoch, where the sync trainer given a phase
of several epochs runs its pipeline on across their ends
(``next_epoch``: an epoch's first batch is then decoded one update stale
too), so a multi-epoch sync phase is one loop over the phase and not this
schedule epoch by epoch. Genuinely decoupled runs are NOT
token-identical to sync: the per-shard RNG fold runs over a different
submesh size — documented, and why strict exists.

Chaos story: the ``rl.actor.step`` injection point takes the
``actor_preempt`` fault kind (resilience/chaos.py). Preemption of an actor
device sheds it from the submesh plan, recounts the in-flight ring entries
under the survivors, and re-broadcasts; when no actor survives (or the
roles share one device), the epoch falls back to the sync schedule on the
learner submesh. The same point takes ``host_rejoin`` in the grow-back
direction: a previously-shed device re-admits via
:func:`~cst_captioning_tpu.parallel.submesh.grow_actors` (membership and
order restored from the pristine initial plan), the ring re-binds to the
grown submesh, and in-flight rollouts from the degraded period are drained
and deterministically recounted in order — the same drop-and-recount
spelling as the shrink. Drain: ``should_stop`` persists the in-flight ring as a
``seam.npz``-style blob (the trainer's ``_seam_bytes`` ring format) and a
resume replays those exact tokens — strict-mode drains hold bit-identity
(the depth-1 ring is empty between steps), decoupled drains are
replay-consistent.
"""

from __future__ import annotations

import functools
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cst_captioning_tpu import obs
from cst_captioning_tpu.config.config import RLConfig
from cst_captioning_tpu.decoding import fused_decode, sample_decode
from cst_captioning_tpu.parallel.submesh import (
    SubmeshPlan,
    grow_actors,
    plan_submesh,
    shared_plan,
    shrink_actors,
)
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.rl.rewards import RewardComputer
from cst_captioning_tpu.rl.scst import SCSTTrainer
from cst_captioning_tpu.train.state import TrainState

# pending actor-slice preemptions (chaos `actor_preempt` faults land here;
# the epoch loop services them at the next rl.actor.step)
_PREEMPT_REQUESTS: list[int] = []

# pending actor-slice rejoins (chaos `host_rejoin` at rl.actor.step lands
# here; the epoch loop services them at its next batch boundary)
_REJOIN_REQUESTS: list[int] = []


def request_actor_preempt(slice_index=None) -> None:
    """Mark one actor device (by index into the current actor submesh) as
    preempted. Called by the chaos harness's ``actor_preempt`` kind; the
    running :class:`AsyncSCSTTrainer` epoch services the request at its
    next ``rl.actor.step`` visit."""
    _PREEMPT_REQUESTS.append(0 if slice_index is None else int(slice_index))


def request_actor_rejoin(slice_index=None) -> None:
    """Inverse of :func:`request_actor_preempt`: re-admit one previously
    shed actor device (by index into the INITIAL actor submesh — the
    pristine plan, so a preempt/rejoin pair addressing the same index
    round-trips the same device). Called by the chaos harness's
    ``host_rejoin`` kind when fired at ``rl.actor.step``."""
    _REJOIN_REQUESTS.append(0 if slice_index is None else int(slice_index))


def make_actor_decode(model, mesh: Mesh | None, num_rollouts: int,
                      temperature: float = 1.0, max_len: int | None = None,
                      axis: str = "data", with_greedy: bool = True):
    """Jitted actor decode: (params, feats, masks, rng) ->
    (greedy [B,T] | None, samples [K,B,T], sample_lps [K,B,T]).

    Token streams are bit-identical to ``make_rl_decode`` /
    ``make_parallel_rl_decode`` on the same mesh — it is the same fused
    program (the per-lane logprobs already exist inside the scan; this
    factory just stops discarding the sampled lanes') — which is what lets
    strict mode pin against the sync loop's decode."""

    def device_decode(params, feats, masks, rng, batch_axes=()):
        if with_greedy:
            greedy, _, samples, lps = fused_decode(
                model, params, feats, masks, rng,
                num_rollouts=num_rollouts, temperature=temperature,
                max_len=max_len, batch_axes=batch_axes,
            )
            return greedy, samples, lps
        samples, lps = sample_decode(
            model, params, feats, masks, rng,
            num_rollouts=num_rollouts, temperature=temperature,
            max_len=max_len, batch_axes=batch_axes,
        )
        return samples, lps

    if mesh is None:
        fn = jax.jit(device_decode)
    else:
        def sharded(params, feats, masks, rng):
            local_rng = jax.random.fold_in(rng, jax.lax.axis_index(axis))
            return device_decode(
                params, feats, masks, local_rng, batch_axes=(axis,)
            )

        out_specs = (
            (P(axis), P(None, axis), P(None, axis)) if with_greedy
            else (P(None, axis), P(None, axis))
        )
        fn = jax.jit(jax.shard_map(
            sharded, mesh=mesh,
            in_specs=(P(), P(axis), P(axis), P()),
            out_specs=out_specs,
        ))
    if with_greedy:
        return fn

    def no_greedy(params, feats, masks, rng):
        samples, lps = fn(params, feats, masks, rng)
        return None, samples, lps

    return no_greedy


class RolloutRing:
    """Device-resident ring of decoded rollout batches (the actor->learner
    handoff buffer; depth 2 is the double buffer).

    Storage is three preallocated stacked device buffers — sampled tokens
    [D,K,B,T], their logprobs [D,K,B,T], and (greedy baseline) [D,B,T] —
    written in place by a DONATING jitted slot update: each push consumes
    the previous buffer and rebinds the attribute, so the ring's HBM
    footprint is exactly ``depth`` batches for the epoch regardless of how
    many batches stream through (graftlint GL017 tracks this donate-through-
    ``self._write``/rebind-``self._tokens`` shape — the attribute-rooted
    donation case). Per-entry host metadata (RNG key, params version, batch
    refs, video ids) rides in a deque; the device arrays never leave the
    ring until :meth:`pop` reads a slot out for consumption.
    """

    def __init__(self, depth: int, mesh: Mesh | None = None,
                 axis: str = "data"):
        self.depth = max(1, int(depth))
        self.mesh = mesh
        self.axis = axis
        self._tokens = None      # [D, K, B, T] sampled tokens
        self._lps = None         # [D, K, B, T] sample logprobs
        self._greedy = None      # [D, B, T] greedy baseline (optional)
        self._meta: deque = deque()
        self._slot = 0

    @staticmethod
    @functools.partial(jax.jit, donate_argnums=(0,))
    def _write(buf, update, slot):
        return jax.lax.dynamic_update_index_in_dim(buf, update, slot, 0)

    @staticmethod
    @jax.jit
    def _read(buf, slot):
        return jax.lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)

    def __len__(self) -> int:
        return len(self._meta)

    def _alloc(self, like, spec):
        buf = jnp.zeros((self.depth,) + like.shape, like.dtype)
        if self.mesh is not None:
            buf = jax.device_put(buf, NamedSharding(self.mesh, spec))
        return buf

    def push(self, greedy, samples, lps, **meta) -> None:
        """Write one decoded batch into the next slot (donating the ring
        buffers) and queue its metadata. Batch shapes must be constant
        across the epoch (the video-mode batcher wrap-pads, so they are)."""
        slot = self._slot
        self._slot = (slot + 1) % self.depth
        if self._tokens is None:
            self._tokens = self._alloc(samples, P(None, None, self.axis))
            self._lps = self._alloc(lps, P(None, None, self.axis))
            if greedy is not None:
                self._greedy = self._alloc(greedy, P(None, self.axis))
        self._tokens = self._write(self._tokens, samples, slot)
        self._lps = self._write(self._lps, lps, slot)
        if greedy is not None:
            self._greedy = self._write(self._greedy, greedy, slot)
        self._meta.append(dict(slot=slot, **meta))

    def pop(self):
        """Oldest entry -> (meta, greedy, samples, lps) device arrays."""
        meta = self._meta.popleft()
        slot = meta["slot"]
        greedy = (
            None if self._greedy is None else self._read(self._greedy, slot)
        )
        return meta, greedy, self._read(self._tokens, slot), \
            self._read(self._lps, slot)

    def entries(self):
        """Every in-flight entry, oldest first, WITHOUT consuming (the
        seam-capture read)."""
        for meta in list(self._meta):
            slot = meta["slot"]
            greedy = (
                None if self._greedy is None
                else self._read(self._greedy, slot)
            )
            yield meta, greedy, self._read(self._tokens, slot), \
                self._read(self._lps, slot)

    def drain_meta(self) -> list[dict]:
        """Drop the device buffers (an actor submesh died under them) and
        return the orphaned metadata so the caller can recount each entry
        from its stored RNG key."""
        metas = list(self._meta)
        self._meta.clear()
        self._tokens = self._lps = self._greedy = None
        self._slot = 0
        return metas


class AsyncSCSTTrainer(SCSTTrainer):
    """SCSTTrainer with the actor/learner split epoch schedule.

    The parent's reward/advantage/update halves are reused verbatim —
    ``self.mesh`` (and therefore ``_score``/``_apply``'s host transfers and
    the update factory) is the LEARNER submesh; the actor side gets its own
    decode closure on the actor submesh and a :class:`RolloutRing`. With
    ``mesh=None`` or in strict mode both roles share one mesh and the
    schedule degenerates to the sequential sync loop (the bit-identity pin).

    Multihost actor slices and async broadcast over DCN are explicitly out
    of scope here (ROADMAP carry-overs): the split is within one process's
    devices.
    """

    # staleness-in-updates buckets: small integers, not latencies
    _STALE_BUCKETS = (0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0)

    def __init__(self, model, reward: RewardComputer, cfg: RLConfig,
                 mesh: Mesh | None = None, max_len: int | None = None,
                 donate: bool = False, guard: bool = False, retry=None,
                 on_event=None, comm=None, stats: bool = False,
                 strict: bool = False, batch_size: int = 0,
                 axis: str = "data"):
        depth = max(1, int(getattr(cfg, "rollout_depth", 2)))
        bound = max(0, int(getattr(cfg, "staleness_bound", 1)))
        # depth 1 + bound 0 IS the strict sequential schedule — honor it
        # implicitly so config-driven strict runs need no extra flag
        implicit = depth == 1 and bound == 0
        self._strict = bool(strict) or implicit
        if strict:
            # replay whichever schedule the sync loop runs: its default
            # 1-deep pipeline is exactly a depth-2 ring (decode(i) lands one
            # update stale, update(i-1) dispatches after decode(i));
            # pipelined=False is the depth-1 sequential ring
            depth = 2 if getattr(cfg, "pipelined", True) else 1
            bound = depth - 1
        elif implicit:
            depth, bound = 1, 0
        self._axis = axis
        self._full_mesh = mesh
        self._batch_size = int(batch_size)
        if mesh is None:
            plan = None
        elif self._strict:
            plan = shared_plan(mesh, axis=axis)
        else:
            plan = plan_submesh(
                mesh, getattr(cfg, "actor_fraction", 0.5), axis=axis,
                batch_size=batch_size,
            )
        self._plan = plan
        # the pristine plan: grow-back restores membership/order from it,
        # minus whatever the dead-actor ledger still names as lost
        self._initial_plan = plan
        self._dead_actors: set = set()
        lmesh = mesh if plan is None or plan.shared else plan.learner
        super().__init__(
            model, reward, cfg, mesh=lmesh, max_len=max_len, donate=donate,
            guard=guard, retry=retry, on_event=on_event, comm=comm,
            stats=stats,
        )
        self._max_len = max_len
        self._wg = cfg.baseline == "greedy"
        self._depth = depth
        self._bound = bound
        self._actor_mesh = None if plan is None else plan.actor
        self._actor_decode = make_actor_decode(
            model, self._actor_mesh, cfg.num_rollouts, cfg.temperature,
            max_len, axis=axis, with_greedy=self._wg,
        )
        self._fallback_sync = False
        self._actor_params = None
        self._actor_version = -1
        self._learner_version = 0
        # per-epoch ledgers the bench and the recovery tests read back
        self.last_staleness: dict[int, int] = {}
        self.last_dropped = 0
        self.last_rejoined = 0
        self.last_occupancy: dict[str, float] = {}

    # ---- submesh plumbing ---------------------------------------------------

    def _shared_roles(self) -> bool:
        return (
            self._fallback_sync or self._plan is None or self._plan.shared
        )

    def _to_actor(self, tree, spec):
        # an unconditional reshard: a same-sharding device_put is a no-op,
        # and the sync FALLBACK still needs full-mesh inputs pulled down
        # onto the learner submesh even though the roles then "share" it
        if self._actor_mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self._actor_mesh, spec))

    def _to_learner(self, tree, spec):
        if self.mesh is None:
            return tree
        return jax.device_put(tree, NamedSharding(self.mesh, spec))

    def _refresh_actor(self, state: TrainState) -> None:
        """Broadcast the learner's current params actor-ward. Shared-role
        layouts just rebind (the strict path: the decode must see the SAME
        arrays the sync loop would); split layouts reshard a copy onto the
        actor submesh so the learner's buffer donation can't invalidate
        in-flight actor reads."""
        if self._actor_version == self._learner_version:
            return
        with obs.span("rl.actor.broadcast"):
            p = state.params
            if not self._shared_roles():
                p = jax.device_put(p, NamedSharding(self._actor_mesh, P()))
            self._actor_params = p
        self._actor_version = self._learner_version

    def _dispatch_decode(self, feats, masks, srng):
        """One actor decode dispatch -> (greedy, samples, lps) on the actor
        submesh (no host sync — the transfer out happens at consumption)."""
        feats_a = self._to_actor(feats, P(self._axis))
        masks_a = self._to_actor(masks, P(self._axis))
        if self._actor_mesh is not None:
            srng = jax.device_put(
                srng, NamedSharding(self._actor_mesh, P())
            )
        with obs.span("rl.actor.decode"):
            out = self._actor_decode(self._actor_params, feats_a, masks_a,
                                     srng)
        obs.counter("rl.actor.batches").inc()
        return out

    # ---- chaos: actor preemption -------------------------------------------

    def _service_preemptions(self) -> list[dict]:
        """Apply pending ``actor_preempt`` requests: shrink the actor
        submesh (or fall back to sync when nothing survives), rebuild the
        actor decode, and return the orphaned ring metadata for recount."""
        lost: list[dict] = []
        while _PREEMPT_REQUESTS:
            idx = _PREEMPT_REQUESTS.pop(0)
            obs.counter("rl.actor.preempted").inc()
            if self._fallback_sync:
                continue
            lost.extend(self._ring.drain_meta())
            new_plan = None
            if self._plan is not None and not self._plan.shared:
                devs = self._plan.actor_devices
                self._dead_actors.add(devs[idx % len(devs)])
                new_plan = shrink_actors(
                    self._plan, idx, axis=self._axis,
                    batch_size=self._batch_size,
                )
            if new_plan is None:
                self._fallback_sync = True
                self._actor_mesh = self.mesh
                self.on_event(
                    "rl_actor_fallback_sync", recount=len(lost),
                )
            else:
                self._plan = new_plan
                self._actor_mesh = new_plan.actor
                self.on_event(
                    "rl_actor_degraded", survivors=new_plan.n_actors,
                    recount=len(lost),
                )
            self._actor_decode = make_actor_decode(
                self.model, self._actor_mesh, self.cfg.num_rollouts,
                self.cfg.temperature, self._max_len, axis=self._axis,
                with_greedy=self._wg,
            )
            # drained ring reallocates on the survivors' mesh at next push
            self._ring.mesh = self._actor_mesh
            self._actor_version = -1    # survivors need a fresh broadcast
        return lost

    def _service_rejoins(self) -> list[dict]:
        """Apply pending ``host_rejoin`` requests: grow the actor submesh
        back toward its initial plan (climbing out of the sync fallback if
        that is where the shrinks left us), re-bind the ring to the grown
        submesh, rebuild the actor decode, and return the orphaned ring
        metadata so in-flight rollouts from the degraded period are
        deterministically recounted in order — the shrink's drop-and-recount
        spelling, run in the grow direction."""
        lost: list[dict] = []
        while _REJOIN_REQUESTS:
            idx = _REJOIN_REQUESTS.pop(0)
            obs.counter("rl.actor.rejoined").inc()
            init = self._initial_plan
            if init is None or init.shared:
                continue    # nothing was ever split; nothing to grow
            device = init.actor_devices[idx % len(init.actor_devices)]
            self._dead_actors.discard(device)
            new_plan = grow_actors(
                None if self._fallback_sync else self._plan, device, init,
                axis=self._axis, batch_size=self._batch_size,
                dead=self._dead_actors,
            )
            if new_plan is None:
                continue    # already present — a duplicate rejoin is a no-op
            lost.extend(self._ring.drain_meta())
            self._fallback_sync = False
            self._plan = new_plan
            self._actor_mesh = new_plan.actor
            self.last_rejoined += 1
            self.on_event(
                "rl_actor_regrown", actors=new_plan.n_actors,
                recount=len(lost),
            )
            self._actor_decode = make_actor_decode(
                self.model, self._actor_mesh, self.cfg.num_rollouts,
                self.cfg.temperature, self._max_len, axis=self._axis,
                with_greedy=self._wg,
            )
            # drained ring reallocates on the grown mesh at next push
            self._ring.mesh = self._actor_mesh
            self._actor_version = -1    # the rejoiner needs the broadcast
        return lost

    # ---- drain-aware ring seam ---------------------------------------------

    def _seam_capture_ring(self) -> dict:
        """Host copies of every in-flight ring entry (tokens, logprobs, RNG
        key data, params version) — the decoupled loop's drain payload."""
        ring = []
        for meta, greedy, samples, lps in self._ring.entries():
            # one explicit batched readback per entry; this runs once per
            # drain (not per step), depth entries at most
            toks, logps, key = jax.device_get(  # graftlint: disable=GL001 (drain path: at most rollout_depth entries, once per preemption save)
                (samples, lps, jax.random.key_data(meta["rng"]))
            )
            e = {
                "samples": toks,
                "lps": logps,
                "video_ids": [str(v) for v in meta["video_ids"]],
                "valid": meta["valid_np"],    # host float32 (_valid_np)
                "rng": key,
                "batch_index": int(meta["batch_index"]),
            }
            if greedy is not None:
                e["greedy"] = jax.device_get(greedy)
            ring.append(e)
        return {"ring": ring}

    def _replay_entry(self, entry: dict, feats, masks, video_ids, valid_np,
                      batch_index: int) -> None:
        """Push one persisted seam entry back into the ring as if it had
        just been decoded: tokens/logprobs come from the blob (decoded
        pre-drain — replay-consistent), the stored RNG key keeps a later
        drop/recount deterministic, and the version is the CURRENT actor
        version so the replayed work isn't immediately dropped."""
        spec_kbt = P(None, self._axis)
        samples = entry["samples"]
        lps = entry["lps"]
        greedy = entry.get("greedy")
        if self._actor_mesh is not None:
            sh = NamedSharding(self._actor_mesh, spec_kbt)
            samples = jax.device_put(samples, sh)
            lps = jax.device_put(lps, sh)
            if greedy is not None:
                greedy = jax.device_put(
                    greedy, NamedSharding(self._actor_mesh, P(self._axis))
                )
        else:
            samples = jnp.asarray(samples)
            lps = jnp.asarray(lps)
            if greedy is not None:
                greedy = jnp.asarray(greedy)
        rng = jax.random.wrap_key_data(jnp.asarray(entry["rng"]))
        self._ring.push(
            greedy, samples, lps, rng=rng, version=self._actor_version,
            feats=feats, masks=masks, video_ids=video_ids,
            valid_np=valid_np, batch_index=batch_index,
            t_disp=time.perf_counter(),
        )

    # ---- the decoupled epoch ------------------------------------------------

    def train_epoch(self, state: TrainState, batches, rng, on_step=None,
                    pipelined: bool = True, should_stop=None,
                    seam: dict | None = None,
                    seam_sink: dict | None = None):
        """Actor/learner epoch. The two-stage ``pipelined`` flag is
        subsumed by the ring schedule and ignored. Contract matches the
        parent: every batch not persisted into ``seam_sink`` gets exactly
        one applied update, so the returned state corresponds to
        ``len(metrics)`` completed steps."""
        del pipelined
        if self.mesh is not None:
            rng = jax.device_put(rng, NamedSharding(self.mesh, P()))
        # a split layout's update runs on the learner submesh: pull the
        # (replicated) state down onto it; it is pushed back to the full
        # mesh on return so checkpoints/eval see the caller's layout
        state = self._to_learner(state, P())
        out: list[dict] = []

        def emit(m):
            out.append(m)
            if on_step is not None:
                on_step(m)

        _PREEMPT_REQUESTS.clear()
        _REJOIN_REQUESTS.clear()
        self._ring = RolloutRing(
            self._depth, mesh=self._actor_mesh, axis=self._axis
        )
        self._actor_params = None
        self._actor_version = -1
        self._learner_version = 0
        self.last_staleness = {}
        self.last_dropped = 0
        self.last_rejoined = 0
        replay: deque = deque(
            seam.get("ring", []) if seam else []
        )
        t0 = time.perf_counter()
        busy = {"actor": 0.0, "learner": 0.0}
        last_done = {"actor": t0, "learner": t0}
        pending_update = None       # (dispatch_time, metrics ref)

        def flush_update():
            nonlocal pending_update
            if pending_update is None:
                return
            t_disp, ref = pending_update
            pending_update = None
            jax.block_until_ready(ref)
            now = time.perf_counter()
            busy["learner"] += now - max(t_disp, last_done["learner"])
            last_done["learner"] = now

        def consume(state, meta, greedy, samples, lps):
            """Score + update one ring entry on the learner submesh,
            dropping and recounting it first if its params are stale."""
            nonlocal pending_update
            with obs.span("rl.learner.step"):
                stale = self._learner_version - meta["version"]
                if stale > self._bound:
                    obs.counter("rl.staleness.dropped").inc()
                    self.last_dropped += 1
                    # recount: refresh the actor to the learner's version
                    # and re-decode with the entry's OWN rng key — the
                    # token stream depends only on (params, rng), so two
                    # runs drop and recount identically
                    self._refresh_actor(state)
                    greedy, samples, lps = self._dispatch_decode(
                        meta["feats"], meta["masks"], meta["rng"]
                    )
                    meta = dict(meta, version=self._actor_version,
                                t_disp=time.perf_counter())
                    stale = self._learner_version - meta["version"]
                self.last_staleness[stale] = (
                    self.last_staleness.get(stale, 0) + 1
                )
                obs.histogram("rl.staleness", self._STALE_BUCKETS).observe(
                    float(stale)
                )
                # host-observed actor busy window: dispatch -> tokens ready
                # (clipped against the previous window so queued decodes
                # don't double-count)
                t_wait = time.perf_counter()
                jax.block_until_ready(samples)
                now = time.perf_counter()
                busy["actor"] += now - max(
                    min(meta["t_disp"], t_wait), last_done["actor"]
                )
                last_done["actor"] = now
                greedy_l = self._to_learner(greedy, P(self._axis))
                samples_l = self._to_learner(samples, P(None, self._axis))
                feats_l = self._to_learner(meta["feats"], P(self._axis))
                masks_l = self._to_learner(meta["masks"], P(self._axis))
                scored = self._score(
                    greedy_l, samples_l, feats_l, masks_l,
                    meta["video_ids"], meta["valid_np"],
                )
                flush_update()
                t_disp = time.perf_counter()
                state, m = self._apply(state, *scored)
            obs.counter("rl.learner.steps").inc()
            self._learner_version += 1
            emit(m)
            pending_update = (t_disp, m.get("rl_loss"))
            return state

        stopped = False
        batch_index = -1
        for feats, masks, video_ids, valid in batches:
            batch_index += 1
            if should_stop is not None and should_stop():
                stopped = True
                break
            if not self._fallback_sync:
                chaos.visit("rl.actor.step")
            # rejoins first: a rejoin+preempt landing on the same boundary
            # grows then shrinks, in that deterministic order
            lost = self._service_rejoins() + self._service_preemptions()
            if lost:
                # recount the orphaned in-flight rollouts under whatever
                # decodes now (survivor actors, or the learner submesh in
                # the sync fallback), in original order
                for meta in lost:
                    self._refresh_actor(state)
                    g, s, l = self._dispatch_decode(
                        meta["feats"], meta["masks"], meta["rng"]
                    )
                    meta = dict(meta, version=self._actor_version,
                                t_disp=time.perf_counter())
                    state = consume(state, meta, g, s, l)
            if self._fallback_sync:
                # sync schedule on the learner submesh: the parent's strict
                # sequential step, same per-batch rng chain
                rng, srng = jax.random.split(rng)
                state, m = self.train_step(
                    state, self._to_learner(feats, P(self._axis)),
                    self._to_learner(masks, P(self._axis)),
                    video_ids, srng, valid,
                )
                self._learner_version += 1
                emit(m)
                continue
            self._refresh_actor(state)
            rng, srng = jax.random.split(rng)
            valid_np = self._valid_np(valid, len(video_ids))
            if replay and list(replay[0]["video_ids"]) == [
                str(v) for v in video_ids
            ]:
                self._replay_entry(
                    replay.popleft(), feats, masks, video_ids, valid_np,
                    batch_index,
                )
            else:
                if replay:
                    # changed data order: never marry old tokens to new
                    # features — fall through to a live decode
                    self.on_event("seam_ring_discarded", entries=len(replay))
                    replay.clear()
                greedy, samples, lps = self._dispatch_decode(
                    feats, masks, srng
                )
                self._ring.push(
                    greedy, samples, lps, rng=srng,
                    version=self._actor_version, feats=feats, masks=masks,
                    video_ids=video_ids, valid_np=valid_np,
                    batch_index=batch_index, t_disp=time.perf_counter(),
                )
            while len(self._ring) >= self._depth:
                state = consume(state, *self._ring.pop())
        if stopped and seam_sink is not None and len(self._ring):
            # drain-aware stop: the in-flight buffer persists instead of
            # being consumed — the resume replays these exact tokens
            seam_sink.update(self._seam_capture_ring())
        else:
            while len(self._ring):
                state = consume(state, *self._ring.pop())
        flush_update()
        wall = max(time.perf_counter() - t0, 1e-9)
        occ = {
            "actor": min(1.0, busy["actor"] / wall),
            "learner": min(1.0, busy["learner"] / wall),
        }
        self.last_occupancy = dict(occ, wall_s=wall)
        obs.gauge("rl.actor.occupancy").set(occ["actor"])
        obs.gauge("rl.learner.occupancy").set(occ["learner"])
        if not self._shared_roles() and self._full_mesh is not None:
            state = jax.device_put(
                state, NamedSharding(self._full_mesh, P())
            )
        return state, out
