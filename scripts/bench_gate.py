"""Gate every committed BENCH_*.json: parse, parity, acceptance, schema.

The bench JSONs are the repo's performance evidence — ROADMAP rounds and
the READMEs cite them — but nothing re-validated them after commit: a
bench edited to emit a new schema, a parity bool that silently flipped
false, or a truncated file from a killed run would all sit in the tree
unnoticed. This gate (run by scripts/lint.sh) re-reads every one and
enforces the invariants the benches themselves promise:

- the file parses as JSON (no torn writes);
- every ``parity`` block's booleans are ALL true, and every
  ``*_token_match_frac`` in one is >= 0.9 (the bf16 near-tie argmax
  allowance the decode benches document — anything lower is a real
  selection bug, not tie noise);
- ``parity_ok``, where present, is true;
- ``acceptance`` blocks and ``vs_*`` comparison fields hold either real
  measurements (numbers / dicts of true booleans) or a machine-checkable
  skip reason (a string starting with ``"skipped"``) — never false, never
  an unexplained null;
- the flagship summaries carry a ``metric`` name, and any non-TPU rerun
  carries the standard TPU-rerun ``note`` so a CPU number can never be
  mistaken for the committed TPU operating point.

Exit nonzero on the first file with violations, listing all of them.
"""

from __future__ import annotations

import glob
import json
import numbers
import os
import sys


def _check_parity(path: str, key: str, block, errors: list[str]) -> None:
    if not isinstance(block, dict):
        errors.append(f"{path}: {key} is not a dict")
        return
    for k, v in block.items():
        if isinstance(v, bool):
            if not v:
                errors.append(f"{path}: {key}.{k} is false")
        elif k.endswith("_token_match_frac"):
            if not (isinstance(v, numbers.Real) and v >= 0.9):
                errors.append(
                    f"{path}: {key}.{k} = {v!r} below the 0.9 tie-noise "
                    "floor"
                )


def _check_acceptance(path: str, key: str, v, errors: list[str]) -> None:
    """Acceptance values: number (a measured ratio), true bool, a dict of
    acceptance values, or a ``skipped*`` reason string."""
    if isinstance(v, bool):
        if not v:
            errors.append(f"{path}: {key} is false")
    elif isinstance(v, numbers.Real):
        pass
    elif isinstance(v, str):
        if not v.startswith("skipped"):
            errors.append(
                f"{path}: {key} = {v!r} is neither a measurement nor a "
                "'skipped*' reason"
            )
    elif isinstance(v, dict):
        for k2, v2 in v.items():
            _check_acceptance(path, f"{key}.{k2}", v2, errors)
    else:
        errors.append(f"{path}: {key} = {v!r} (unexpected acceptance type)")


def _walk(path: str, node, errors: list[str], key: str = "") -> None:
    if isinstance(node, dict):
        for k, v in node.items():
            sub = f"{key}.{k}" if key else k
            if k == "parity":
                _check_parity(path, sub, v, errors)
            elif k == "parity_ok":
                if v is not True:
                    errors.append(f"{path}: {sub} = {v!r} (must be true)")
            elif k == "acceptance" or k.startswith("vs_"):
                _check_acceptance(path, sub, v, errors)
            else:
                _walk(path, v, errors, sub)
    elif isinstance(node, list):
        for i, v in enumerate(node):
            _walk(path, v, errors, f"{key}[{i}]")


def check_file(path: str) -> list[str]:
    errors: list[str] = []
    try:
        with open(path, encoding="utf-8") as f:
            data = json.load(f)
    except (OSError, ValueError) as e:
        return [f"{path}: does not parse as JSON ({e})"]

    name = os.path.basename(path)

    # flagship summaries: every bench names what it measured — a headline
    # "metric" field, or (the recipe ledger) nested *metrics* tables
    def _has_metric(node) -> bool:
        if isinstance(node, dict):
            return any("metric" in k for k in node) or any(
                _has_metric(v) for v in node.values()
            )
        if isinstance(node, list):
            return any(_has_metric(v) for v in node)
        return False

    if not _has_metric(data):
        errors.append(f"{path}: no metric-naming field (flagship schema)")
    # a non-TPU measurement must say so: the note is what stops a CPU
    # number from being read as the committed TPU operating point
    device = str(
        data.get("device_kind")
        or (data.get("summary") or {}).get("device_kind", "")
        if isinstance(data.get("summary"), dict) else data.get("device_kind")
        or ""
    )
    if device and "tpu" not in device.lower():
        note = data.get("note") or (
            (data.get("summary") or {}).get("note", "")
            if isinstance(data.get("summary"), dict) else ""
        )
        if not note:
            errors.append(
                f"{path}: non-TPU device_kind {device!r} without the "
                "TPU-rerun 'note' field"
            )
    if name == "BENCH_RL_ASYNC.json":
        _check_rl_async(path, data, errors)
    if name == "BENCH_RL_ONLINE.json":
        _check_rl_online(path, data, errors)
    if name == "BENCH_SERVING.json":
        _check_serving(path, data, errors)
    if name == "BENCH_SCALING.json":
        _check_scaling(path, data, errors)
    _walk(path, data, errors)
    return errors


def _check_rl_async(path: str, data: dict, errors: list[str]) -> None:
    """The decoupled-RL ledger's own promises beyond the generic schema:
    the strict rung proves the replay (the parity block carries all three
    strict_* pins — _check_parity then enforces they are true), and the
    decoupled rung carries its async evidence (staleness histogram,
    dropped/recounted count, actor+learner occupancy)."""
    parity = data.get("parity")
    if not isinstance(parity, dict):
        errors.append(f"{path}: missing the strict parity block")
    else:
        for k in ("strict_params_bit_exact", "strict_scored_tokens_bit_exact",
                  "strict_nothing_dropped"):
            if k not in parity:
                errors.append(f"{path}: parity block missing {k!r}")
    rung = (data.get("rungs") or {}).get("decoupled")
    if not isinstance(rung, dict):
        errors.append(f"{path}: missing the 'decoupled' rung")
        return
    if not isinstance(rung.get("staleness_histogram"), dict):
        errors.append(f"{path}: decoupled rung missing staleness_histogram")
    if not isinstance(rung.get("dropped_stale"), int):
        errors.append(f"{path}: decoupled rung missing dropped_stale")
    occ = rung.get("occupancy")
    if not isinstance(occ, dict) or not {"actor", "learner"} <= set(occ):
        errors.append(
            f"{path}: decoupled rung occupancy must carry actor + learner"
        )


def _check_rl_online(path: str, data: dict, errors: list[str]) -> None:
    """The serving-as-actor ledger's own promises: the swap-parity block
    carries the hot-swap pins (tokens vs fused_decode, full bit-exact
    fresh-service replay, straddled live traffic, two-run determinism —
    _check_parity then enforces they are true), and the online rung
    carries the closed-loop evidence (update/swap counters, staleness
    drop ledger, reward trend over the seeded trace)."""
    parity = data.get("parity")
    if not isinstance(parity, dict):
        errors.append(f"{path}: missing the swap-parity block")
    else:
        for k in ("swap_parity_tokens_bit_exact",
                  "swap_parity_replay_bit_exact",
                  "swap_straddled_live_traffic",
                  "two_runs_bit_identical_params"):
            if k not in parity:
                errors.append(f"{path}: parity block missing {k!r}")
    rung = (data.get("rungs") or {}).get("online")
    if not isinstance(rung, dict):
        errors.append(f"{path}: missing the 'online' rung")
        return
    if not isinstance(rung.get("learner_updates"), int):
        errors.append(f"{path}: online rung missing learner_updates")
    if not isinstance(rung.get("dropped_stale"), int):
        errors.append(f"{path}: online rung missing dropped_stale")
    if not isinstance(rung.get("staleness_histogram"), dict):
        errors.append(f"{path}: online rung missing staleness_histogram")
    if not isinstance(rung.get("reward_trend"), list):
        errors.append(f"{path}: online rung missing reward_trend")


def _check_serving(path: str, data: dict, errors: list[str]) -> None:
    """The serving ledger's own promises beyond the generic schema: the
    ``paged_inkernel`` rung ran against its dense-gather reference on both
    trace shapes (its parity block carries the bit-exact pin —
    _check_parity then enforces it is true), the per-stride bank-bytes
    model shows the paged path moving strictly fewer bytes, and the
    stress config's page high-water mark exceeded the dense-bank
    footprint the gather path refuses."""
    paged = data.get("paged")
    if not isinstance(paged, dict):
        errors.append(f"{path}: missing the 'paged' rung")
        return
    traces = paged.get("traces")
    if not isinstance(traces, dict) or not traces:
        errors.append(f"{path}: paged rung missing traces")
    else:
        for tname, t in traces.items():
            for leg in ("paged_inkernel", "dense_gather"):
                if not isinstance((t or {}).get(leg), dict) or \
                        "goodput_rps" not in t[leg]:
                    errors.append(
                        f"{path}: paged.traces.{tname} missing the "
                        f"{leg!r} leg"
                    )
    parity = paged.get("parity")
    if not isinstance(parity, dict) or \
            "paged_vs_gather_bit_exact" not in parity:
        errors.append(
            f"{path}: paged rung missing the paged_vs_gather_bit_exact "
            "parity pin"
        )
    bb = paged.get("per_stride_bank_bytes")
    if not isinstance(bb, dict) or not (
        isinstance(bb.get("paged_inkernel"), numbers.Real)
        and isinstance(bb.get("dense_gather"), numbers.Real)
        and bb["paged_inkernel"] < bb["dense_gather"]
    ):
        errors.append(
            f"{path}: paged.per_stride_bank_bytes must show the paged "
            "path moving strictly fewer bytes than the dense gather"
        )
    stress = paged.get("stress")
    if not isinstance(stress, dict):
        errors.append(f"{path}: paged rung missing the stress block")
    else:
        hwm = stress.get("pages_hwm")
        foot = stress.get("dense_footprint_pages")
        if not (isinstance(hwm, numbers.Real)
                and isinstance(foot, numbers.Real) and hwm > foot):
            errors.append(
                f"{path}: paged.stress pages_hwm = {hwm!r} must exceed "
                f"dense_footprint_pages = {foot!r} (otherwise the pool "
                "never held more than one batch's dense-bank worth)"
            )


def _check_scaling(path: str, data: dict, errors: list[str]) -> None:
    """The scaling ledger's own promises beyond the generic schema: the
    dp weak-scaling points survive (bench_scaling.py merges, never drops),
    and the flagship-XL ``mp`` block carries an mp>1 rung with the analytic
    vocab-shard merge bytes, its parity block carries both bit-exact pins
    (_check_parity then enforces they are true), the embedding-grad
    dp-allreduce ledger shows the mp-sharded payload strictly below the
    replicated one, and the CPU-mesh caveat note is present."""
    if not isinstance(data.get("points"), list) or not data["points"]:
        errors.append(f"{path}: dp weak-scaling 'points' vanished")
    mp = data.get("mp")
    if not isinstance(mp, dict):
        errors.append(f"{path}: missing the flagship-XL 'mp' block")
        return
    rungs = mp.get("rungs")
    if not isinstance(rungs, list) or not any(
        isinstance(r, dict) and r.get("mp", 1) > 1 for r in rungs
    ):
        errors.append(f"{path}: mp block has no mp>1 rung")
    else:
        for r in rungs:
            if r.get("mp", 1) > 1 and not isinstance(
                r.get("merge_bytes_per_step_per_device"), dict
            ):
                errors.append(
                    f"{path}: mp={r.get('mp')} rung missing the analytic "
                    "merge_bytes_per_step_per_device model"
                )
    parity = mp.get("parity")
    if not isinstance(parity, dict):
        errors.append(f"{path}: mp block missing its parity block")
    else:
        for k in ("stride_tokens_bit_exact", "beam_candidates_bit_exact"):
            if k not in parity:
                errors.append(f"{path}: mp parity block missing {k!r}")
    led = mp.get("embedding_grad_ledger")
    if not isinstance(led, dict) or not (
        isinstance(led.get("mp1_bytes_on_wire_per_update"), numbers.Real)
        and isinstance(led.get("mp2_bytes_on_wire_per_update"), numbers.Real)
        and led["mp2_bytes_on_wire_per_update"]
        < led["mp1_bytes_on_wire_per_update"]
    ):
        errors.append(
            f"{path}: mp.embedding_grad_ledger must show the mp-sharded "
            "dp-allreduce strictly below the replicated payload"
        )
    if not mp.get("note"):
        errors.append(f"{path}: mp block missing the CPU-mesh 'note'")


def main(argv: list[str]) -> int:
    root = argv[1] if len(argv) > 1 else "."
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if not paths:
        print(f"bench_gate: no BENCH_*.json under {root!r}", file=sys.stderr)
        return 1
    all_errors: list[str] = []
    for p in paths:
        all_errors.extend(check_file(p))
    if all_errors:
        for e in all_errors:
            print(f"bench_gate: {e}", file=sys.stderr)
        print(f"bench_gate: FAIL — {len(all_errors)} violation(s) across "
              f"{len(paths)} file(s)", file=sys.stderr)
        return 1
    print(f"bench_gate: {len(paths)} bench JSON(s) clean", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
