#!/usr/bin/env bash
# Pre-commit gate: graftlint + a full bytecode compile + runtime smokes.
#
#   scripts/lint.sh
#
# Exits nonzero on (a) any NEW graftlint finding — baselined findings pass,
# see graftlint.baseline — or a stale baseline entry / unused inline
# suppression (--check-stale), or an UNFIXED autofixable finding
# (--fix-check: the repair is mechanical, so run
# `python -m cst_captioning_tpu.tools.graftlint --fix` and commit), or the
# two-pass lint exceeding its 3 s budget; (b) any file that doesn't
# byte-compile; (c) the obs_report / chaos / sanitizer smokes failing.
# tier-1 runs the same graftlint check via tests/test_graftlint.py
# (test_repo_is_graftlint_clean), so CI cannot drift from this script.
set -euo pipefail
cd "$(dirname "$0")/.."

# Fast pre-commit path first: pass 1 still builds (and warms) the full
# whole-program index, pass 2 runs only on files git says changed vs HEAD —
# sub-second on a one-file diff, so a dirty tree fails in the cheap pass
# before the authoritative full-tree gate below spends its budget.
python -m cst_captioning_tpu.tools.graftlint --changed-only --timings

# Two-pass AST analysis only — no JAX backend, no device. Pass 1 builds the
# whole-program project index (mtime-keyed summary cache keeps repeat runs
# warm; now carrying the per-function axis environments, donation facts,
# and the shape/dtype/sharding environments that power GL016–GL020),
# pass 2 runs the per-file + interprocedural rules. --timings prints the
# per-pass line; --budget asserts index+rules stay under 3 s (bumped
# from 2 s as the tree grew past ~145 files; still catches a rule or
# cache regression, which costs 10x, not 10%). This
# full-tree line stays the authoritative gate — --changed-only above is
# only the fast path.
python -m cst_captioning_tpu.tools.graftlint \
    cst_captioning_tpu tests scripts chip_smoke.py \
    --fix-check --check-stale --timings --budget 3

# catches syntax errors in files graftlint may not reach (non-.py-suffixed
# entry points aside, this is the whole tree)
python -m compileall -q cst_captioning_tpu tests scripts chip_smoke.py

# obs_report smoke check: the report CLI must aggregate a known-good run dir
# without a jax import or backend init (it is part of the operator loop for
# dead runs — it has to work on a box with nothing but the repo)
python -m cst_captioning_tpu.cli.obs_report tests/fixtures/obs_run > /dev/null

# postmortem smoke: the flight-recorder bundle renderer (manifest verify +
# ring timeline) against the committed fixture bundle — same no-jax
# contract; dead-run triage must work anywhere
python -m cst_captioning_tpu.cli.obs_report \
    --postmortem tests/fixtures/postmortem_bundle > /dev/null

# fleet-postmortem smoke: merge the committed 2-proc fixture (manifest
# verify on every bundle, skew correction, trip attribution) and enumerate
# its bundles — obs/fleet.py shares the no-jax contract, pinned here
python -m cst_captioning_tpu.cli.obs_report \
    --postmortem tests/fixtures/postmortem_fleet > /dev/null
python -m cst_captioning_tpu.cli.obs_report \
    --postmortem tests/fixtures/postmortem_fleet --list > /dev/null

# elastic chaos smoke: seeded shrink->regrow scenario on 2 simulated
# hosts — kill host 1 mid-RL-epoch, re-admit it through the rejoin
# marker seam, finish on the FULL mesh with a contiguous step clock and
# finite dynamics (README "Elastic training", grow-back half)
JAX_PLATFORMS=cpu python scripts/chaos_smoke.py > /dev/null

# runtime sanitizer smoke: the hot-path tier-1 subset under
# jax.transfer_guard("disallow") + jax.debug_nans — the empirical half of
# GL001/GL013's zero-implicit-transfer claim (README "Static analysis")
JAX_PLATFORMS=cpu scripts/sanitize.sh > /dev/null

echo "lint.sh: OK"
