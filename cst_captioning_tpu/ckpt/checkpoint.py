"""Durable TrainState checkpoints via flax.serialization msgpack.

Layout per checkpoint name (``best`` / ``latest`` / ``step_00001200``):

    <dir>/<name>/state.msgpack   — params + opt state + step + rng
    <dir>/<name>/infos.json      — epoch, phase, batch_index, config snapshot
    <dir>/<name>/manifest.json   — sha256 + size per file, verified on load

msgpack via ``flax.serialization`` (not pickle) keeps checkpoints
language-neutral and safe to load. Durability (resilience/durable.py):
every file is fsync'd, the tmp dir is fsync'd, the swap is ``os.replace``,
and the parent dir is fsync'd after — a host crash at ANY instant leaves
either the old or the new checkpoint fully intact. An existing checkpoint is
demoted to ``<name>.prev`` (not deleted) before the swap, so even the
replace window and a post-"success" torn write have a fallback generation.
"""

from __future__ import annotations

import errno
import json
import os
import re
import shutil
from typing import Any, Callable, Mapping

import jax
from flax import serialization

from cst_captioning_tpu import obs
from cst_captioning_tpu.resilience import chaos
from cst_captioning_tpu.resilience.durable import (
    CorruptCheckpointError,
    MANIFEST_FILE,
    fsync_dir,
    verify_manifest,
    write_bytes_durable,
    write_manifest,
)
from cst_captioning_tpu.resilience.retry import RetryPolicy, retry_call
from cst_captioning_tpu.train.state import TrainState

STATE_FILE = "state.msgpack"
INFOS_FILE = "infos.json"

_STEP_NAME_RE = re.compile(r"^step_(\d+)$")


def _is_prng_key(x) -> bool:
    return hasattr(x, "dtype") and jax.dtypes.issubdtype(x.dtype, jax.dtypes.prng_key)


def _keys_to_data(tree):
    """Typed PRNG keys -> raw uint32 key data (msgpack can't hold key dtypes)."""
    return jax.tree.map(
        lambda x: jax.random.key_data(x) if _is_prng_key(x) else x, tree
    )


def host_copy(state):
    """The explicit device->host read-back of a state about to be saved.
    Typed keys leave the device as raw key data: ``jax.device_get`` of a
    typed key wraps what it read into a new key *on the device*, an implicit
    host->device transfer (``jax.transfer_guard`` vetoes it in the epoch
    loops) for a value :func:`save_state` unwraps again anyway."""
    return jax.device_get(_keys_to_data(state))


def host_copy_begin(state) -> None:
    """Start :func:`host_copy`'s transfers and return at once: each leaf's
    device->host copy is queued behind the program that writes it, so a
    caller with more device work to dispatch (the primed RL pipeline, after
    an epoch's last update) lets the read-back run under that work, and the
    ``host_copy`` that follows finds the copies done or in flight. Typed
    keys are left to ``host_copy`` (it reads their raw data, a new array),
    and so is an array that spans processes."""
    for x in jax.tree.leaves(state):
        if (isinstance(x, jax.Array) and x.is_fully_addressable
                and not _is_prng_key(x)):
            x.copy_to_host_async()


def _data_to_keys(loaded, template):
    """Re-wrap raw key data as typed keys wherever the template has them."""
    return jax.tree.map(
        lambda t, x: jax.random.wrap_key_data(x) if _is_prng_key(t) else x,
        template,
        loaded,
    )


def save_state(ckpt_dir: str, name: str, state: TrainState,
               infos: dict[str, Any] | None = None,
               extra_files: Mapping[str, bytes | Callable[[], bytes]]
               | None = None) -> str:
    """Durably write state+infos under ``ckpt_dir/name``; returns the path.

    ``extra_files`` (name -> bytes, or a callable that gives them and is
    called here, once the state is written) ride along in the same atomic
    swap and are covered by the manifest — the drain-aware RL seam
    (``seam.npz``) uses this so the seam tokens can never outlive or predate
    the state they belong to.

    CONTRACT: one writer per ``ckpt_dir`` at a time — crash-atomic (a kill
    mid-save leaves the previous generation intact: only the stale ``.tmp``
    is lost, reclaimed by the next save; a kill inside the swap leaves the
    demoted ``<name>.prev``), not concurrency-atomic. Multi-host runs
    satisfy this via the Trainer's process-0 checkpoint gate."""
    final = os.path.join(ckpt_dir, name)
    tmp = final + ".tmp"
    chaos.visit("ckpt.save")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    # fully materialize on host before serializing
    host_state = host_copy(state)
    state_bytes = serialization.to_bytes(host_state)
    infos_bytes = json.dumps(infos or {}, indent=2, default=float).encode()
    write_bytes_durable(os.path.join(tmp, STATE_FILE), state_bytes)
    chaos.visit("ckpt.state_written")
    write_bytes_durable(os.path.join(tmp, INFOS_FILE), infos_bytes)
    blobs = {STATE_FILE: state_bytes, INFOS_FILE: infos_bytes}
    for extra_name, blob in (extra_files or {}).items():
        if extra_name in blobs or os.sep in extra_name:
            raise ValueError(f"bad extra checkpoint file name {extra_name!r}")
        if callable(blob):
            blob = blob()
        write_bytes_durable(os.path.join(tmp, extra_name), blob)
        blobs[extra_name] = blob
    write_manifest(tmp, blobs)
    fsync_dir(tmp)
    chaos.visit("ckpt.pre_replace")
    if os.path.exists(final):
        # demote, don't delete: the previous generation survives both a
        # crash inside this swap and a latent torn write in the new files
        prev = final + ".prev"
        if os.path.exists(prev):
            shutil.rmtree(prev)
        os.replace(final, prev)
    os.replace(tmp, final)
    fsync_dir(ckpt_dir)
    return final


def load_state(ckpt_dir: str, name: str, template: TrainState) -> tuple[TrainState, dict]:
    """Restore a full TrainState (shape/dtype from ``template``) + infos.

    Verifies the manifest checksums first (when present — legacy checkpoints
    without one load unverified); raises
    :class:`~cst_captioning_tpu.resilience.durable.CorruptCheckpointError`
    on any mismatch instead of deserializing a torn file."""
    path = os.path.join(ckpt_dir, name)
    verify_manifest(path)
    data_template = _keys_to_data(jax.device_get(template))
    with open(os.path.join(path, STATE_FILE), "rb") as f:
        loaded = serialization.from_bytes(data_template, f.read())
    state = _data_to_keys(loaded, template)
    infos = {}
    infos_path = os.path.join(path, INFOS_FILE)
    if os.path.exists(infos_path):
        with open(infos_path) as f:
            infos = json.load(f)
    return state, infos


def load_params(ckpt_dir: str, name: str, params_template) -> Any:
    """Params-only restore — the XE -> RL handoff (fresh optimizer)."""
    path = os.path.join(ckpt_dir, name)
    verify_manifest(path)
    with open(os.path.join(path, STATE_FILE), "rb") as f:
        blob = f.read()
    state_dict = serialization.msgpack_restore(blob)
    return serialization.from_state_dict(params_template, state_dict["params"])


def _read_infos(path: str) -> dict:
    """Best-effort infos.json read for candidate ordering (not for load)."""
    try:
        with open(os.path.join(path, INFOS_FILE), encoding="utf-8") as f:
            return json.load(f)
    except (OSError, ValueError):
        return {}


class CheckpointManager:
    """best-by-metric + latest policy, mid-epoch ``step_*`` checkpoints with
    keep-last-K rotation, and checksum-verified auto-resume (SURVEY.md §5)."""

    def __init__(self, ckpt_dir: str, metric: str = "CIDEr-D", mode: str = "max",
                 keep: int = 3, log: Callable[..., None] | None = None,
                 retry: RetryPolicy | None = None):
        self.ckpt_dir = ckpt_dir
        self.metric = metric
        self.mode = mode
        self.keep = keep
        self.log = log or (lambda event, **fields: None)
        self.retry = retry or RetryPolicy()
        self.best_value: float | None = None
        os.makedirs(ckpt_dir, exist_ok=True)
        # recover best_value from an existing best checkpoint (resume case)
        best_infos = os.path.join(ckpt_dir, "best", INFOS_FILE)
        if os.path.exists(best_infos):
            with open(best_infos) as f:
                self.best_value = json.load(f).get("best_value")

    def _improved(self, value: float) -> bool:
        if self.best_value is None:
            return True
        return value > self.best_value if self.mode == "max" else value < self.best_value

    def _save(self, name: str, state: TrainState, infos: dict,
              extra_files: Mapping[str, bytes] | None = None) -> str:
        """One durable save with jittered-backoff retries on transient I/O.

        ENOSPC gets a reclaim step before each retry: the oldest ``step_*``
        generation (then any demoted ``*.prev``) is deleted — a full disk
        costs the oldest history, never the run — with a structured
        ``ckpt_enospc`` event + ``resilience.ckpt_enospc`` counter."""

        def attempt():
            try:
                return save_state(
                    self.ckpt_dir, name, state, infos,
                    extra_files=extra_files,
                )
            except OSError as e:
                if getattr(e, "errno", None) == errno.ENOSPC:
                    freed = self._reclaim_space(exclude=name)
                    obs.counter("resilience.ckpt_enospc").inc()
                    self.log(
                        "ckpt_enospc", name=name, freed=freed, detail=str(e),
                    )
                raise

        # the span covers retries + backoff sleeps: its dur IS the stall a
        # save inflicts on the step loop (the "ckpt" phase of the report)
        with obs.span("ckpt.save", ckpt=name):
            return retry_call(
                attempt,
                policy=self.retry,
                on_retry=lambda info: self.log("ckpt_retry", name=name, **info),
            )

    def _reclaim_space(self, exclude: str = "") -> list[str]:
        """Free checkpoint-dir space for an ENOSPC retry: oldest ``step_*``
        generation first, demoted ``*.prev`` generations next. Never touches
        ``best``/``latest`` or the checkpoint being written."""
        victims: list[str] = []
        for _, step_name in self.step_checkpoints():
            if step_name != exclude:
                victims.append(step_name)
                break
        if not victims:
            victims = sorted(
                e for e in os.listdir(self.ckpt_dir)
                if e.endswith(".prev") and e != f"{exclude}.prev"
                and os.path.isdir(os.path.join(self.ckpt_dir, e))
            )[:1]
        for victim in victims:
            shutil.rmtree(
                os.path.join(self.ckpt_dir, victim), ignore_errors=True
            )
        return victims

    def save(self, state: TrainState, value: float | None = None,
             infos: dict | None = None) -> bool:
        """Save 'latest' always; promote to 'best' when the metric improves.

        ``infos["extra_files"]``, where given, is taken out of the infos and
        written beside the state in the same atomic swap, as
        :meth:`save_step`'s ``extra_files`` are (:func:`save_state` has
        their form): the tokens of the batch the primed RL pipeline decoded
        across the epoch's end (``seam.npz``). TEMPORARY route: it rides in
        ``infos`` because the call's signature is a seam to the benchmark,
        whose stand-in for this class takes these three; once that stand-in
        takes ``extra_files=`` this method does too (ROADMAP S4).

        Returns True when a new best was recorded.
        """
        infos = dict(infos or {})
        extra_files = infos.pop("extra_files", None)
        improved = value is not None and self._improved(value)
        if improved:
            self.best_value = float(value)
        # both checkpoints carry the post-update best so 'latest' metadata
        # never lags 'best' (ADVICE r1)
        infos["best_value"] = self.best_value
        self._save("latest", state, infos, extra_files=extra_files)
        if improved:
            self._save("best", state, infos, extra_files=extra_files)
        return improved

    def save_step(self, state: TrainState, step: int,
                  infos: dict | None = None,
                  extra_files: Mapping[str, bytes] | None = None) -> str:
        """Mid-epoch ``step_<n>`` checkpoint + keep-last-``keep`` rotation."""
        infos = dict(infos or {})
        infos.setdefault("global_step", int(step))
        infos["best_value"] = self.best_value
        path = self._save(
            f"step_{int(step):08d}", state, infos, extra_files=extra_files
        )
        if self.keep > 0:
            for _, name in self.step_checkpoints()[:-self.keep]:
                shutil.rmtree(
                    os.path.join(self.ckpt_dir, name), ignore_errors=True
                )
        return path

    def step_checkpoints(self) -> list[tuple[int, str]]:
        """Existing ``step_*`` checkpoint (step, dirname) pairs, ascending."""
        out = []
        for entry in os.listdir(self.ckpt_dir):
            m = _STEP_NAME_RE.match(entry)
            if m and os.path.isdir(os.path.join(self.ckpt_dir, entry)):
                out.append((int(m.group(1)), entry))
        return sorted(out)

    def _candidates(self) -> list[str]:
        """Restore candidates, newest first.

        Ordered by the recorded ``global_step`` (epoch-end and mid-epoch
        saves share one clock), tie-broken by role: an in-flight ``latest``
        beats a ``step_*`` beats ``best`` beats any demoted ``*.prev``
        generation. Legacy checkpoints without ``global_step`` sort last in
        role order — exactly the old latest-then-best behavior."""
        rank = {"latest": 3, "best": 1}
        cands = []
        for entry in sorted(os.listdir(self.ckpt_dir)):
            path = os.path.join(self.ckpt_dir, entry)
            if entry.endswith(".tmp") or not os.path.isdir(path):
                continue
            if not os.path.exists(os.path.join(path, STATE_FILE)):
                continue
            base = entry[:-5] if entry.endswith(".prev") else entry
            role = 0 if entry.endswith(".prev") else (
                rank.get(base, 2 if _STEP_NAME_RE.match(base) else 0)
            )
            step = _read_infos(path).get("global_step")
            cands.append((-1 if step is None else int(step), role, entry))
        return [e for _, _, e in sorted(cands, reverse=True)]

    def restore_latest(self, template: TrainState,
                       prefer: str | None = None) -> tuple[TrainState, dict] | None:
        """Auto-resume: newest checkpoint that passes verification.

        A corrupt/partial candidate is never silently skipped: each failure
        is logged as a structured ``ckpt_corrupt`` event (candidate name,
        error class, detail) AND counts on ``resilience.ckpt_corrupt``
        before falling back to the next generation.

        ``prefer`` names a candidate to try FIRST regardless of rank: the
        elastic drain paths pass the seam checkpoint they just wrote, whose
        phase-local step ordinal may sort below an older epoch-end save —
        the ranked order remains the fallback if it fails verification."""
        with obs.span("ckpt.restore"):
            cands = self._candidates()
            if prefer is not None and prefer in cands:
                cands = [prefer] + [c for c in cands if c != prefer]
            for name in cands:
                try:
                    state, infos = load_state(self.ckpt_dir, name, template)
                    # which candidate won matters to the caller (sidecar
                    # files like the RL seam live next to the state)
                    infos.setdefault("ckpt_name", name)
                    return state, infos
                except Exception as e:
                    obs.counter("resilience.ckpt_corrupt").inc()
                    self.log(
                        "ckpt_corrupt",
                        name=name,
                        error=type(e).__name__,
                        detail=str(e),
                    )
                    continue  # verified-corrupt (and logged): try the next
            return None
