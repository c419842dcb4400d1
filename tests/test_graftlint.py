"""graftlint: per-rule positive/negative fixtures, baseline round-trip,
--json schema, and the tier-1 self-check that keeps the repo lint-clean.

Pure AST analysis — nothing here touches a JAX backend except the
import-cleanliness subprocess test at the bottom (which exists to PROVE no
backend comes up).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from cst_captioning_tpu.tools.graftlint import Baseline, all_rules, lint_paths
from cst_captioning_tpu.tools.graftlint.cli import main as cli_main

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# every lintable top-level target of the repo (scripts/lint.sh mirrors this)
REPO_LINT_PATHS = [
    os.path.join(REPO, p)
    for p in ("cst_captioning_tpu", "tests", "scripts", "chip_smoke.py")
]


# deliberately lint-dirty cross-file fixture pairs (skipped by the repo
# walk — "fixtures" is in core._SKIP_DIRS — and linted explicitly here)
FIXTURES = os.path.join(REPO, "tests", "fixtures", "graftlint")


def _lint(tmp_path, relname: str, source: str, rules=None):
    path = tmp_path / relname
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(source)
    # cache_path="": unit fixtures rewrite files faster than mtime
    # granularity; the cache has its own dedicated tests
    result = lint_paths([str(path)], str(tmp_path), rule_ids=rules,
                        cache_path="")
    return result.findings


def _lint_fixture(sub: str, rules, only: str | None = None):
    root = os.path.join(FIXTURES, sub)
    paths = [os.path.join(root, only)] if only else [root]
    return lint_paths(paths, root, rule_ids=rules, cache_path="").findings


def _rules_of(findings):
    return sorted({f.rule for f in findings})


# ---- GL001: host sync -------------------------------------------------------

def test_gl001_positive_sync_in_traced_function(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)\n"
    ), rules=["GL001"])
    assert _rules_of(findings) == ["GL001"]
    assert findings[0].severity == "error"


def test_gl001_positive_sync_in_scan_body(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "def outer(xs):\n"
        "    def body(c, x):\n"
        "        return c, float(x)\n"
        "    return jax.lax.scan(body, 0, xs)\n"
    ), rules=["GL001"])
    assert _rules_of(findings) == ["GL001"]


def test_gl001_negative_sync_outside_trace(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return x * 2\n"
        "def host(x):\n"
        "    return np.asarray(step(x))\n"
    ), rules=["GL001"])
    assert findings == []


def test_gl001_positive_per_step_loop_sync(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/train/fake_loop.py", (
            "import jax\n"
            "def epoch(step, batches, log):\n"
            "    for b in batches:\n"
            "        state, m = step(b)\n"
            "        log.append(float(m['loss']))\n"
        ), rules=["GL001"],
    )
    assert _rules_of(findings) == ["GL001"]
    assert findings[0].severity == "warning"


def test_gl001_negative_gated_loop_sync(tmp_path):
    # a sync inside a log-every-N `if` body is amortized — not flagged
    findings = _lint(
        tmp_path, "cst_captioning_tpu/train/fake_loop.py", (
            "import jax\n"
            "def epoch(step, batches, log, every):\n"
            "    n = 0\n"
            "    for b in batches:\n"
            "        state, m = step(b)\n"
            "        n += 1\n"
            "        if every and n % every == 0:\n"
            "            log.append(float(m['loss']))\n"
        ), rules=["GL001"],
    )
    assert findings == []


def test_gl001_negative_loop_sync_outside_hot_packages(tmp_path):
    # same loop in a host-side package: scoring IS a readback, not flagged
    findings = _lint(
        tmp_path, "cst_captioning_tpu/metrics/fake.py", (
            "import jax\n"
            "def score(rows):\n"
            "    out = []\n"
            "    for r in rows:\n"
            "        out.append(float(r))\n"
            "    return out\n"
        ), rules=["GL001"],
    )
    assert findings == []


# ---- GL002: PRNG key reuse --------------------------------------------------

def test_gl002_positive_key_reuse(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "def rollout(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.uniform(key, (2,))\n"
        "    return a + b\n"
    ), rules=["GL002"])
    assert _rules_of(findings) == ["GL002"]
    assert "line 3" in findings[0].message


def test_gl002_negative_split_between_consumers(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "def rollout(key):\n"
        "    k1, k2 = jax.random.split(key)\n"
        "    a = jax.random.normal(k1, (2,))\n"
        "    key, sub = jax.random.split(k2)\n"
        "    b = jax.random.uniform(sub, (2,))\n"
        "    c = jax.random.normal(key, (2,))\n"
        "    return a + b + c\n"
    ), rules=["GL002"])
    assert findings == []


def test_gl002_negative_rebound_key(tmp_path):
    # consuming, REBINDING, then consuming again is the canonical pattern
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "def loop(key, n):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    key = jax.random.fold_in(key, 1)\n"
        "    b = jax.random.normal(key, (2,))\n"
        "    return a + b\n"
    ), rules=["GL002"])
    assert findings == []


def test_gl002_not_applied_in_tests(tmp_path):
    # determinism assertions reuse keys on purpose
    findings = _lint(tmp_path, "tests/test_fake.py", (
        "import jax\n"
        "def test_deterministic(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.normal(key, (2,))\n"
        "    assert (a == b).all()\n"
    ), rules=["GL002"])
    assert findings == []


# ---- GL003: Python branch on traced value -----------------------------------

def test_gl003_positive_if_on_jnp_value(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    s = jnp.sum(x)\n"
        "    if s > 0:\n"
        "        return x\n"
        "    return -x\n"
    ), rules=["GL003"])
    assert _rules_of(findings) == ["GL003"]


def test_gl003_positive_while_on_lax_value(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "@jax.jit\n"
        "def f(x):\n"
        "    while jax.lax.reduce_max(x) > 0:\n"
        "        x = x - 1\n"
        "    return x\n"
    ), rules=["GL003"])
    assert _rules_of(findings) == ["GL003"]


def test_gl003_negative_static_branch(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def make(with_greedy):\n"
        "    @jax.jit\n"
        "    def f(x):\n"
        "        if with_greedy:\n"
        "            return jnp.sum(x)\n"
        "        return x\n"
        "    return f\n"
    ), rules=["GL003"])
    assert findings == []


# ---- GL004: jit step without donation ---------------------------------------

def test_gl004_positive_undonated_train_step(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "@jax.jit\n"
        "def train_step(state, batch):\n"
        "    return state\n"
    ), rules=["GL004"])
    assert _rules_of(findings) == ["GL004"]


def test_gl004_negative_explicit_donation(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def train_step(state, batch):\n"
        "    return state\n"
        "def make_update(fn, donate):\n"
        "    return jax.jit(fn, donate_argnums=(0,) if donate else ())\n"
    ), rules=["GL004"])
    assert findings == []


def test_gl004_negative_stateless_decode_step(tmp_path):
    # a decode 'step' carries no train state: donation buys nothing
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "@jax.jit\n"
        "def step(params, feats):\n"
        "    return feats\n"
    ), rules=["GL004"])
    assert findings == []


# ---- GL005: f32 literal in bf16 module --------------------------------------

def test_gl005_positive_f32_literal_in_models(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/models/fake.py", (
            "import jax.numpy as jnp\n"
            "def forward(x):\n"
            "    bias = jnp.zeros((4,), jnp.float32)\n"
            "    return x + bias\n"
        ), rules=["GL005"],
    )
    assert _rules_of(findings) == ["GL005"]


def test_gl005_negative_config_dtype_and_out_of_scope(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/models/fake.py", (
            "import jax.numpy as jnp\n"
            "def forward(x, cfg):\n"
            "    bias = jnp.zeros((4,), jnp.dtype(cfg.dtype))\n"
            "    return x + bias\n"
        ), rules=["GL005"],
    )
    assert findings == []
    # f32 input data built in tests/benches is fine (the model casts)
    findings = _lint(
        tmp_path, "tests/test_fake.py", (
            "import jax.numpy as jnp\n"
            "x = jnp.zeros((4,), jnp.float32)\n"
        ), rules=["GL005"],
    )
    assert findings == []


# ---- GL006: heavy imports / import-time device work -------------------------

def test_gl006_positive_torch_import(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/train/fake.py",
        "import torch\n", rules=["GL006"],
    )
    assert _rules_of(findings) == ["GL006"]


def test_gl006_positive_module_level_device_work(tmp_path):
    findings = _lint(tmp_path, "bench_fake.py", (
        "import jax\n"
        "N = len(jax.devices())\n"
    ), rules=["GL006"])
    assert _rules_of(findings) == ["GL006"]


def test_gl006_negative_guarded_and_function_scoped(tmp_path):
    findings = _lint(tmp_path, "bench_fake.py", (
        "import jax\n"
        "import numpy as np\n"
        "def main():\n"
        "    return len(jax.devices())\n"
        "if __name__ == '__main__':\n"
        "    print(jax.devices())\n"
    ), rules=["GL006"])
    assert findings == []


# ---- GL007: partition-rule coverage -----------------------------------------

_CONTRACT = {"params": ["params/lstm0/kernel", "params/orphan/bias"]}


def _write_contract(tmp_path, params):
    p = tmp_path / "scripts" / "shardings_contract.json"
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(json.dumps({"params": params}))


def test_gl007_positive_unmatched_rule_and_unruled_param(tmp_path):
    _write_contract(tmp_path, _CONTRACT["params"])
    findings = _lint(tmp_path, "mesh_fake.py", (
        "PARAM_PARTITION_RULES = (\n"
        "    ('lstm', r'params/lstm\\d+/.*', None),\n"
        "    ('ghost', r'params/ghost/.*', None),\n"
        ")\n"
        "SHARDING_CONTRACT = 'scripts/shardings_contract.json'\n"
    ), rules=["GL007"])
    messages = " | ".join(f.message for f in findings)
    assert len(findings) == 2
    assert "ghost" in messages and "params/orphan/bias" in messages


def test_gl007_negative_full_coverage(tmp_path):
    _write_contract(tmp_path, ["params/lstm0/kernel", "params/out/bias"])
    findings = _lint(tmp_path, "mesh_fake.py", (
        "PARAM_PARTITION_RULES = (\n"
        "    ('lstm', r'params/lstm\\d+/.*', None),\n"
        "    ('head', r'params/out/.*', None),\n"
        ")\n"
        "SHARDING_CONTRACT = 'scripts/shardings_contract.json'\n"
    ), rules=["GL007"])
    assert findings == []


def test_gl007_missing_contract_is_info_not_gate(tmp_path):
    findings = _lint(tmp_path, "mesh_fake.py", (
        "PARAM_PARTITION_RULES = (('lstm', r'.*', None),)\n"
        "SHARDING_CONTRACT = 'scripts/shardings_contract.json'\n"
    ), rules=["GL007"])
    assert [f.severity for f in findings] == ["info"]


# ---- GL008: TPU-only test imports without slow marker -----------------------

def test_gl008_positive_unmarked_tpu_test(tmp_path):
    findings = _lint(tmp_path, "tests/test_fake_pallas.py", (
        "from jax.experimental.pallas import tpu as pltpu\n"
        "def test_kernel():\n"
        "    pass\n"
    ), rules=["GL008"])
    assert _rules_of(findings) == ["GL008"]


def test_gl008_negative_slow_marked(tmp_path):
    findings = _lint(tmp_path, "tests/test_fake_pallas.py", (
        "import pytest\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "pytestmark = pytest.mark.slow\n"
        "def test_kernel():\n"
        "    pass\n"
    ), rules=["GL008"])
    assert findings == []
    findings = _lint(tmp_path, "tests/test_fake_pallas2.py", (
        "import pytest\n"
        "from jax.experimental.pallas import tpu as pltpu\n"
        "@pytest.mark.slow\n"
        "def test_kernel():\n"
        "    pass\n"
    ), rules=["GL008"])
    assert findings == []


# ---- GL009: silently swallowed broad exceptions -----------------------------

def test_gl009_positive_swallowed_continue(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/ckpt/fake.py", (
            "def restore(candidates):\n"
            "    for c in candidates:\n"
            "        try:\n"
            "            return load(c)\n"
            "        except Exception:\n"
            "            continue\n"
        ), rules=["GL009"],
    )
    assert _rules_of(findings) == ["GL009"]
    assert findings[0].severity == "warning"


def test_gl009_positive_bare_except_pass(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/utils/fake.py", (
            "def close(fh):\n"
            "    try:\n"
            "        fh.close()\n"
            "    except:\n"
            "        pass\n"
        ), rules=["GL009"],
    )
    assert _rules_of(findings) == ["GL009"]


def test_gl009_positive_tuple_containing_exception(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/data/fake.py", (
            "def read(path):\n"
            "    try:\n"
            "        return open(path).read()\n"
            "    except (OSError, Exception):\n"
            "        pass\n"
        ), rules=["GL009"],
    )
    assert _rules_of(findings) == ["GL009"]


def test_gl009_negative_logged_fallback_and_narrow_types(tmp_path):
    # logging before falling back is exactly the prescribed fix
    findings = _lint(
        tmp_path, "cst_captioning_tpu/ckpt/fake.py", (
            "def restore(candidates, log):\n"
            "    for c in candidates:\n"
            "        try:\n"
            "            return load(c)\n"
            "        except Exception as e:\n"
            "            log('ckpt_corrupt', name=c, error=str(e))\n"
            "            continue\n"
        ), rules=["GL009"],
    )
    assert findings == []
    # a narrow exception type is a deliberate contract, even when silent
    findings = _lint(
        tmp_path, "cst_captioning_tpu/data/fake.py", (
            "import queue\n"
            "def drain(q):\n"
            "    try:\n"
            "        q.get_nowait()\n"
            "    except queue.Empty:\n"
            "        pass\n"
        ), rules=["GL009"],
    )
    assert findings == []


def test_gl009_not_applied_outside_package(tmp_path):
    # tests/benches swallow on purpose when asserting failure modes
    findings = _lint(
        tmp_path, "tests/test_fake.py", (
            "def test_x():\n"
            "    try:\n"
            "        boom()\n"
            "    except Exception:\n"
            "        pass\n"
        ), rules=["GL009"],
    )
    assert findings == []


# ---- GL010: ad-hoc timing / bare print in package hot paths -----------------

def test_gl010_positive_time_time_in_package(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/train/fake.py", (
            "import time\n"
            "def epoch(step, batches):\n"
            "    t0 = time.time()\n"
            "    for b in batches:\n"
            "        step(b)\n"
            "    return time.time() - t0\n"
        ), rules=["GL010"],
    )
    assert _rules_of(findings) == ["GL010"]
    assert len(findings) == 2 and findings[0].severity == "warning"
    assert "obs.span" in findings[0].message


def test_gl010_positive_bare_print_in_package(tmp_path):
    findings = _lint(
        tmp_path, "cst_captioning_tpu/rl/fake.py", (
            "def score(rows):\n"
            "    print('scored', len(rows))\n"
        ), rules=["GL010"],
    )
    assert _rules_of(findings) == ["GL010"]
    assert "EventLogger" in findings[0].message


def test_gl010_negative_perf_counter_and_obs_span(tmp_path):
    # the prescribed replacements never trip the rule
    findings = _lint(
        tmp_path, "cst_captioning_tpu/train/fake.py", (
            "import time\n"
            "from cst_captioning_tpu import obs\n"
            "def epoch(step, batches):\n"
            "    t0 = time.perf_counter()\n"
            "    with obs.span('xe.epoch'):\n"
            "        for b in batches:\n"
            "            step(b)\n"
            "    obs.event('done', dur=time.perf_counter() - t0)\n"
        ), rules=["GL010"],
    )
    assert findings == []


def test_gl010_not_applied_to_clis_tools_tests(tmp_path):
    # user-facing stdout surfaces and tests print/measure on purpose
    for rel in ("cst_captioning_tpu/cli/fake.py",
                "cst_captioning_tpu/tools/graftlint/fake.py",
                "tests/test_fake.py", "scripts/fake.py", "bench_fake.py"):
        findings = _lint(
            tmp_path, rel, (
                "import time\n"
                "def main():\n"
                "    print(time.time())\n"
            ), rules=["GL010"],
        )
        assert findings == [], rel


# ---- suppressions -----------------------------------------------------------

def test_inline_suppression_same_line(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)  # graftlint: disable=GL001 (fixture)\n"
    ), rules=["GL001"])
    assert findings == []


def test_inline_suppression_next_line(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    # graftlint: disable-next-line=GL001\n"
        "    return np.asarray(x)\n"
    ), rules=["GL001"])
    assert findings == []


def test_suppression_of_other_rule_does_not_hide(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)  # graftlint: disable=GL999\n"
    ), rules=["GL001"])
    assert _rules_of(findings) == ["GL001"]


# ---- baseline round-trip ----------------------------------------------------

def test_baseline_round_trip(tmp_path):
    src = (
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)\n"
    )
    path = tmp_path / "mod.py"
    path.write_text(src)
    first = lint_paths([str(path)], str(tmp_path))
    assert len(first.findings) == 1 and not first.findings[0].baselined

    bl_path = tmp_path / "graftlint.baseline"
    bl = Baseline.from_findings(first.findings)
    bl.save(str(bl_path))
    reloaded = Baseline.load(str(bl_path))

    second = lint_paths([str(path)], str(tmp_path), baseline=reloaded)
    assert len(second.findings) == 1
    assert second.findings[0].baselined
    assert second.gating == []

    # a NEW finding on top of the baselined one still gates
    path.write_text(src + (
        "@jax.jit\n"
        "def step2(x):\n"
        "    return np.asarray(x)\n"
    ))
    third = lint_paths(
        [str(path)], str(tmp_path), baseline=Baseline.load(str(bl_path))
    )
    assert len(third.gating) == 1


def test_baseline_preserves_reasons_on_rewrite(tmp_path):
    path = tmp_path / "mod.py"
    path.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x)\n"
    )
    result = lint_paths([str(path)], str(tmp_path))
    bl = Baseline.from_findings(result.findings)
    bl.entries[0]["reason"] = "intentional: fixture"
    rewritten = Baseline.from_findings(result.findings, old=bl)
    assert rewritten.entries[0]["reason"] == "intentional: fixture"


# ---- CLI / --json schema ----------------------------------------------------

def test_cli_json_schema(tmp_path, capsys):
    path = tmp_path / "mod.py"
    path.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x)\n"
    )
    rc = cli_main([str(path), "--root", str(tmp_path), "--json",
                   "--no-baseline"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["version"] == 1 and report["tool"] == "graftlint"
    assert report["files_checked"] == 1
    assert report["counts"]["new"] == 1
    assert report["counts"]["by_rule"] == {"GL001": 1}
    (finding,) = report["findings"]
    assert set(finding) == {
        "rule", "severity", "path", "line", "col", "message", "context",
        "baselined", "fix",
    }
    assert finding["rule"] == "GL001" and finding["line"] == 4
    assert finding["fix"] is None  # GL001 has no mechanical repair
    # the two-pass engine's bookkeeping rides along in the report
    assert report["stale_baseline"] == []
    assert report["unused_suppressions"] == []
    # the fixes block: autofixable counts + the stale classes --fix repairs
    assert set(report["fixes"]) == {
        "autofixable", "by_rule", "stale_suppressions", "stale_baseline",
    }
    assert report["fixes"]["autofixable"] == 0
    timings = report["timings"]
    assert {"index_seconds", "rules_seconds"} <= set(timings)
    assert timings["files"] == 1


def test_cli_write_baseline_then_clean(tmp_path, capsys):
    path = tmp_path / "mod.py"
    path.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x)\n"
    )
    assert cli_main([str(path), "--root", str(tmp_path),
                     "--write-baseline"]) == 0
    capsys.readouterr()
    assert cli_main([str(path), "--root", str(tmp_path)]) == 0


def test_cli_list_rules_names_all_registered(tmp_path, capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rid in ("GL001", "GL002", "GL003", "GL004", "GL005", "GL006",
                "GL007", "GL008", "GL009", "GL010", "GL011", "GL012",
                "GL013", "GL014", "GL015", "GL016", "GL017"):
        assert rid in out


def test_rule_registry_has_at_least_seven_rules():
    rules = all_rules()
    assert len(rules) >= 7
    assert all(r.rationale for r in rules.values())


def test_parse_error_is_reported_not_fatal(tmp_path):
    path = tmp_path / "broken.py"
    path.write_text("def oops(:\n")
    result = lint_paths([str(path)], str(tmp_path))
    assert [f.rule for f in result.findings] == ["GL000"]
    assert result.gating  # syntax errors gate


# ---- GL011: scan-carry dtype drift ------------------------------------------

def test_gl011_positive_scan_carry_cast_drift(tmp_path):
    """A scan body that casts the carry to a dtype different from its
    literal init — the stride-carry hazard this rule exists for."""
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(xs):\n"
        "    def body(c, x):\n"
        "        return (c + x).astype(jnp.bfloat16), x\n"
        "    init = jnp.zeros((4,), jnp.float32)\n"
        "    return jax.lax.scan(body, init, xs)\n"
    ), rules=["GL011"])
    assert _rules_of(findings) == ["GL011"]
    assert findings[0].severity == "error"
    assert "bfloat16" in findings[0].message and "float32" in findings[0].message


def test_gl011_positive_while_loop_ctor_drift(tmp_path):
    """while_loop body rebuilding the carry in a different dtype than the
    (default-f32) init."""
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(n):\n"
        "    def body(c):\n"
        "        return jnp.asarray(c + 1, dtype=jnp.int32)\n"
        "    return jax.lax.while_loop(lambda c: c < n, body, jnp.zeros(()))\n"
    ), rules=["GL011"])
    assert _rules_of(findings) == ["GL011"]


def test_gl011_positive_tuple_carry_positional(tmp_path):
    """Tuple carries compare leaf-by-leaf: only the drifting position
    fires, dtype-matching ones stay quiet."""
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(xs):\n"
        "    def body(c, x):\n"
        "        a, b = c\n"
        "        return (a.astype(jnp.float32), b.astype(jnp.float16)), x\n"
        "    init = (jnp.zeros((2,), jnp.float32), jnp.zeros((2,), jnp.float32))\n"
        "    return jax.lax.scan(body, init, xs)\n"
    ), rules=["GL011"])
    assert len(findings) == 1 and findings[0].rule == "GL011"
    assert "float16" in findings[0].message


def test_gl011_negative_matching_dtype(tmp_path):
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(xs):\n"
        "    def body(c, x):\n"
        "        return (c + x).astype(jnp.float32), x\n"
        "    init = jnp.zeros((4,), jnp.float32)\n"
        "    return jax.lax.scan(body, init, xs)\n"
    ), rules=["GL011"])
    assert findings == []


def test_gl011_negative_unknown_dtypes_stay_quiet(tmp_path):
    """No literal dtype on either side -> out of scope, no guessing (the
    repo's tree.map-built carries must never false-positive)."""
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(xs, init):\n"
        "    def body(c, x):\n"
        "        return jax.tree.map(jnp.add, c, x), None\n"
        "    return jax.lax.scan(body, init, xs)\n"
    ), rules=["GL011"])
    assert findings == []


def test_gl011_negative_nested_def_returns_ignored(tmp_path):
    """Returns inside helpers nested in the body are not the body's carry."""
    findings = _lint(tmp_path, "mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def outer(xs):\n"
        "    def body(c, x):\n"
        "        def helper(v):\n"
        "            return v.astype(jnp.bfloat16)\n"
        "        return c + helper(x).astype(jnp.float32), x\n"
        "    init = jnp.zeros((4,), jnp.float32)\n"
        "    return jax.lax.scan(body, init, xs)\n"
    ), rules=["GL011"])
    assert findings == []


# ---- project index: summary cache + provenance fixpoint ---------------------

def test_summary_cache_invalidation(tmp_path):
    """Edit a file (mtime/size change) -> its summary is recomputed; an
    untouched file is served from the on-disk cache."""
    import time as _time

    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    mod = tmp_path / "m.py"
    mod.write_text(
        "import numpy as np\n"
        "def f():\n"
        "    return np.zeros(3)\n"
    )
    cache = tmp_path / "cache.json"
    idx = ProjectIndex.build([str(mod)], str(tmp_path),
                             cache_path=str(cache))
    assert idx.stats.summarized >= 1 and cache.exists()
    assert not idx.functions["m.f"].returns_device

    idx2 = ProjectIndex.build([str(mod)], str(tmp_path),
                              cache_path=str(cache))
    assert idx2.stats.summarized == 0 and idx2.stats.cached >= 1
    assert not idx2.functions["m.f"].returns_device

    mod.write_text(
        "import jax.numpy as jnp\n"
        "def f():\n"
        "    return jnp.zeros(3)\n"
    )
    future = _time.time() + 10
    os.utime(mod, (future, future))
    idx3 = ProjectIndex.build([str(mod)], str(tmp_path),
                              cache_path=str(cache))
    assert idx3.stats.summarized >= 1
    assert idx3.functions["m.f"].returns_device


def test_index_fixpoint_transitive_device_returns(tmp_path):
    """returns-device provenance propagates through the call graph across
    modules (a -> b -> jnp)."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    (tmp_path / "a.py").write_text(
        "import jax.numpy as jnp\n"
        "def leaf(x):\n"
        "    return jnp.tanh(x)\n"
    )
    (tmp_path / "b.py").write_text(
        "from a import leaf\n"
        "def mid(x):\n"
        "    return leaf(x)\n"
        "def top(x):\n"
        "    return mid(x)\n"
    )
    idx = ProjectIndex.build(
        [str(tmp_path / "a.py"), str(tmp_path / "b.py")],
        str(tmp_path), cache_path="",
    )
    assert idx.functions["a.leaf"].returns_device
    assert idx.functions["b.mid"].returns_device
    assert idx.functions["b.top"].returns_device


# ---- --check-stale: dead baseline entries + dead suppressions ---------------

def test_stale_baseline_entries_reported(tmp_path):
    path = tmp_path / "mod.py"
    path.write_text(
        "import jax\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return float(x)\n"
    )
    live = lint_paths([str(path)], str(tmp_path), cache_path="")
    bl = Baseline.from_findings(live.findings)
    bl.entries.append({
        "rule": "GL001", "path": "mod.py",
        "context": "return np.asarray(ghost)", "count": 1,
        "reason": "the code site was fixed long ago",
    })
    result = lint_paths([str(path)], str(tmp_path), baseline=bl,
                        cache_path="")
    assert result.gating == []  # the live finding is still covered
    assert [e["context"] for e in result.stale_baseline] == [
        "return np.asarray(ghost)"
    ]


def test_unused_suppressions_reported(tmp_path):
    path = tmp_path / "mod.py"
    path.write_text(
        "import jax\n"
        "import numpy as np\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return np.asarray(x)  # graftlint: disable=GL001 (used)\n"
        "def host(x):\n"
        "    return x  # graftlint: disable=GL003 (nothing ever fires here)\n"
    )
    result = lint_paths([str(path)], str(tmp_path), cache_path="")
    assert [(s["line"], s["rule"]) for s in result.unused_suppressions] == [
        (7, "GL003")
    ]


def test_cli_check_stale_gates(tmp_path, capsys):
    path = tmp_path / "mod.py"
    path.write_text(
        "def f(x):\n"
        "    return x  # graftlint: disable=GL001 (dead)\n"
    )
    (tmp_path / "graftlint.baseline").write_text(json.dumps(
        {"version": 1, "entries": []}
    ))
    assert cli_main([str(path), "--root", str(tmp_path)]) == 0
    capsys.readouterr()
    rc = cli_main([str(path), "--root", str(tmp_path), "--check-stale"])
    err = capsys.readouterr().err
    assert rc == 1 and "unused suppression" in err
    # --check-stale without the full rule set is a usage error
    assert cli_main([str(path), "--root", str(tmp_path), "--check-stale",
                     "--rules", "GL001"]) == 2


def test_cli_timings_and_budget(tmp_path, capsys):
    path = tmp_path / "mod.py"
    path.write_text("def f():\n    return 1\n")
    assert cli_main([str(path), "--root", str(tmp_path), "--timings"]) == 0
    err = capsys.readouterr().err
    assert "index" in err and "rules" in err
    # an absurdly small budget must fail the run
    assert cli_main([str(path), "--root", str(tmp_path),
                     "--budget", "0.000001"]) == 1
    assert "budget" in capsys.readouterr().err


# ---- tier-1 self-check: the repo itself stays lint-clean --------------------

def test_repo_is_graftlint_clean(capsys):
    """The acceptance gate: zero non-baselined findings over the tree."""
    rc = cli_main(REPO_LINT_PATHS + ["--root", REPO])
    out = capsys.readouterr()
    assert rc == 0, f"graftlint found new findings:\n{out.out}"


def test_sharding_contract_matches_model():
    """scripts/check_shardings.py default mode: contract + coverage OK."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import check_shardings
    finally:
        sys.path.pop(0)
    assert check_shardings.main([]) == 0


# ---- satellite: drivers import side-effect-free under JAX_PLATFORMS=cpu -----

def test_scripts_import_without_backend_init():
    """chip_smoke.py / verify_parity.py / check_shardings.py must import
    without initializing a JAX backend — graftlint's AST pass must stay the
    only analysis that needs to read them."""
    code = (
        "import sys\n"
        f"sys.path.insert(0, {os.path.join(REPO, 'scripts')!r})\n"
        "import chip_smoke, verify_parity, check_shardings\n"
        "import jax\n"
        "try:\n"
        "    backends = jax._src.xla_bridge._backends\n"
        "except AttributeError:\n"
        "    backends = None\n"
        "assert not backends, 'importing the drivers initialized a backend'\n"
    )
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, env=env,
        capture_output=True, text=True, timeout=240,
    )
    assert proc.returncode == 0, proc.stderr


# ---- GL012: collective-axis-name typos --------------------------------------

def test_gl012_positive_psum_axis_typo(tmp_path):
    """A misspelled mesh axis in a collective is the exact hazard: an
    unbound-axis trace error (or wrong-axis reduction) deep inside
    shard_map."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'dta')\n"
    ), rules=["GL012"])
    assert _rules_of(findings) == ["GL012"]
    assert findings[0].severity == "error"
    assert "'dta'" in findings[0].message


def test_gl012_positive_axis_name_kwarg_and_tuple(tmp_path):
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def f(x):\n"
        "    a = jax.lax.pmean(x, axis_name='sequ')\n"
        "    b = jax.lax.psum(x, ('data', 'seqq'))\n"
        "    return a, b\n"
    ), rules=["GL012"])
    assert len(findings) == 2
    assert all(f.rule == "GL012" for f in findings)


def test_gl012_negative_declared_axes_and_dynamic_names(tmp_path):
    """Axes declared by train/mesh.py pass; dynamic axis expressions are
    out of scope (not statically checkable)."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def f(x, axis):\n"
        "    a = jax.lax.psum(x, 'data')\n"
        "    b = jax.lax.pmean(x, 'seq')\n"
        "    c = jax.lax.axis_index('data')\n"
        "    d = jax.lax.psum(x, axis)\n"
        "    return a, b, c, d\n"
    ), rules=["GL012"])
    assert findings == []


def test_gl012_axes_extracted_from_mesh_py(tmp_path):
    """The allowed set comes from the *axis-parameter defaults declared by
    train/mesh.py under the lint root, not a hardcoded list."""
    mesh = tmp_path / "cst_captioning_tpu" / "train" / "mesh.py"
    mesh.parent.mkdir(parents=True, exist_ok=True)
    mesh.write_text(
        "def make_mesh(num_devices=0, axis='model', seq_axis='pipeline'):\n"
        "    pass\n"
    )
    good = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'model')\n"
    ), rules=["GL012"])
    assert good == []
    bad = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'data')\n"  # not declared by THIS mesh.py
    ), rules=["GL012"])
    assert _rules_of(bad) == ["GL012"]


def test_gl012_negative_tests_out_of_scope(tmp_path):
    findings = _lint(tmp_path, "tests/test_mod.py", (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'i')\n"
    ), rules=["GL012"])
    assert findings == []


def test_gl012_mesh_axes_rescrape_within_one_process(tmp_path):
    """The stale-cache fix: editing train/mesh.py between two lint runs in
    the SAME process must change the allowed axis set (the scrape lives on
    the per-run project index now, not a module-level cache)."""
    import time as _time

    mesh = tmp_path / "cst_captioning_tpu" / "train" / "mesh.py"
    mesh.parent.mkdir(parents=True, exist_ok=True)
    mesh.write_text("def make_mesh(num_devices=0, axis='alpha'):\n    pass\n")
    src = (
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'alpha')\n"
    )
    assert _lint(tmp_path, "cst_captioning_tpu/mod.py", src,
                 rules=["GL012"]) == []
    mesh.write_text("def make_mesh(num_devices=0, axis='beta'):\n    pass\n")
    future = _time.time() + 10
    os.utime(mesh, (future, future))
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", src,
                     rules=["GL012"])
    assert _rules_of(findings) == ["GL012"] and "'alpha'" in findings[0].message


# ---- GL013: implicit host transfers (interprocedural) -----------------------

def test_gl013_cross_file_device_provenance():
    """The acceptance pair: np.asarray / .tolist() on values whose device
    provenance is declared in ANOTHER module (traced-fn result, device-
    yielding prefetch generator); the suppressed twin stays quiet."""
    findings = _lint_fixture("gl013", ["GL013"])
    assert len(findings) == 2
    assert all(f.rule == "GL013" and f.path.endswith("consumer.py")
               for f in findings)
    by_ctx = {f.context: f for f in findings}
    asarray = next(f for c, f in by_ctx.items() if "np.asarray(tokens)" in c)
    tolist = next(f for c, f in by_ctx.items() if ".tolist()" in c)
    # the finding message carries the interprocedural path
    assert "cst_captioning_tpu.producer.decode" in asarray.message
    assert "jit-traced" in asarray.message
    assert "cst_captioning_tpu.producer.prefetched" in tolist.message


def test_gl013_single_file_engine_provably_cannot():
    """Linting the consumer ALONE must find nothing: the provenance facts
    live in producer.py, out of any per-file engine's reach."""
    assert _lint_fixture(
        "gl013", ["GL013"], only="cst_captioning_tpu/consumer.py"
    ) == []


def test_gl013_worker_pool_explicit_readback_is_clean():
    """The eval pipeline's cross-thread readback (device tokens submitted
    to a pool worker that calls ``jax.device_get`` before numpy) must not
    trip GL013: the explicit transfer is the sanctioned spelling, and
    ``pool.submit`` is not a host-conversion sink — a function parameter's
    provenance is unknown, not device."""
    assert _lint_fixture("gl013_pool", ["GL013"]) == []


def test_gl013_branch_sensitive_no_false_positive(tmp_path):
    """A host rebinding in one branch must not inherit the other branch's
    device provenance (the real scst.py seam pattern)."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def seam(samples, mesh):\n"
        "    if mesh is not None:\n"
        "        samples = jax.device_put(samples)\n"
        "    else:\n"
        "        samples = np.asarray(samples)\n"
        "    return np.asarray(samples)\n"
    ), rules=["GL013"])
    assert findings == []


def test_gl013_local_device_provenance_and_explicit_readback(tmp_path):
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def bad(x):\n"
        "    y = jnp.tanh(x)\n"
        "    return np.asarray(y)\n"
        "def good(x):\n"
        "    y = jnp.tanh(x)\n"
        "    return np.asarray(jax.device_get(y))\n"
    ), rules=["GL013"])
    assert len(findings) == 1 and findings[0].line == 6


def test_gl013_not_applied_outside_package(tmp_path):
    # benches/tests/scripts read back on purpose
    findings = _lint(tmp_path, "tests/helper.py", (
        "import jax.numpy as jnp\n"
        "import numpy as np\n"
        "def f(x):\n"
        "    return np.asarray(jnp.tanh(x))\n"
    ), rules=["GL013"])
    assert findings == []


# ---- GL014: cross-function PRNG key reuse -----------------------------------

def test_gl014_cross_file_key_reuse():
    """The acceptance pair: a key spent by a callee (directly, and through
    one extra call hop) then reused by the caller; split/fold_in and the
    suppressed twin stay quiet."""
    findings = _lint_fixture("gl014", ["GL014"])
    assert len(findings) == 2
    assert all(f.rule == "GL014" and f.path.endswith("caller.py")
               for f in findings)
    direct, transitive = findings
    assert "cst_captioning_tpu.keys_lib.sample_rollout" in direct.message
    assert "jax.random.normal" in direct.message
    assert "cst_captioning_tpu.keys_lib.wrapped" in transitive.message


def test_gl014_single_file_engine_provably_cannot():
    assert _lint_fixture(
        "gl014", ["GL014"], only="cst_captioning_tpu/caller.py"
    ) == []


def test_gl014_local_reuse_stays_gl002(tmp_path):
    """Pure same-function double consumption belongs to GL002 — GL014 only
    owns pairs involving a callee, so the two never double-report."""
    src = (
        "import jax\n"
        "def f(key):\n"
        "    a = jax.random.normal(key, (2,))\n"
        "    b = jax.random.uniform(key, (2,))\n"
        "    return a + b\n"
    )
    assert _lint(tmp_path, "mod.py", src, rules=["GL014"]) == []
    assert _rules_of(_lint(tmp_path, "mod.py", src, rules=["GL002"])) == [
        "GL002"
    ]


def test_gl014_not_applied_in_tests(tmp_path):
    findings = _lint(tmp_path, "tests/test_fake.py", (
        "import jax\n"
        "def consume(k):\n"
        "    return jax.random.normal(k, (2,))\n"
        "def test_reuse(key):\n"
        "    a = consume(key)\n"
        "    b = jax.random.uniform(key, (2,))\n"
        "    assert (a != b).any()\n"
    ), rules=["GL014"])
    assert findings == []


# ---- GL015: sharding-spec drift ---------------------------------------------

def test_gl015_cross_file_axis_drift():
    """The acceptance pair: a PartitionSpec literal checked against axes
    declared in the OTHER module (train/mesh.py); declared axes, dynamic
    specs, and the suppressed twin stay quiet."""
    findings = _lint_fixture("gl015", ["GL015"])
    assert len(findings) == 1
    (f,) = findings
    assert f.rule == "GL015" and f.path.endswith("shard_use.py")
    assert "'data'" in f.message
    # the allowed set names the axes that only mesh.py declares
    assert "model" in f.message and "pipeline" in f.message


def test_gl015_repo_axes_pass(tmp_path):
    """With no fixture mesh the default data/seq axes apply — the repo's
    own spec literals must lint clean under them."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "from jax.sharding import PartitionSpec as P\n"
        "def f():\n"
        "    return P('data', 'seq'), P(None), P(('data', 'seq'))\n"
    ), rules=["GL015"])
    assert findings == []
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "from jax.sharding import PartitionSpec as P\n"
        "def f():\n"
        "    return P('model')\n"
    ), rules=["GL015"])
    assert _rules_of(findings) == ["GL015"]


def test_gl015_not_applied_in_tests(tmp_path):
    findings = _lint(tmp_path, "tests/test_mod.py", (
        "from jax.sharding import PartitionSpec as P\n"
        "S = P('i')\n"
    ), rules=["GL015"])
    assert findings == []


# ---- GL016: collective over a declared-but-unbound axis ---------------------

def test_gl016_cross_file_unbound_axis_in_shard_map_called_helper():
    """THE acceptance fixture: 'pipeline' is a declared mesh axis (GL012
    provably cannot flag it), but the only call path into the helper goes
    through a shard_map body binding just 'model' (axis_names=) — the
    axis-environment fixpoint sees that across files."""
    findings = _lint_fixture("gl016", ["GL016"])
    assert len(findings) == 1
    (f,) = findings
    assert f.rule == "GL016" and f.severity == "error"
    assert f.path.endswith("collectives.py")
    assert "'pipeline'" in f.message and "reduce_pipeline" in f.message
    # the message names what the callers DO bind
    assert "model" in f.message


def test_gl016_gl012_provably_cannot_see_the_fixture():
    """GL012's literal-vs-mesh check passes on the whole gl016 pair —
    every axis spelled is either declared (pipeline/model) or visibly
    bound (vmap's 'rollout'): only the scoped rule catches the bug."""
    assert _lint_fixture("gl016", ["GL012"]) == []


def test_gl016_single_file_engine_provably_cannot():
    """Linting the helpers ALONE must find nothing: with no known caller
    the runtime context is unknowable (and the binding lives in
    mapper.py)."""
    assert _lint_fixture(
        "gl016", ["GL016"], only="cst_captioning_tpu/collectives.py"
    ) == []


def test_gl016_bound_axis_and_suppressed_twin_quiet():
    findings = _lint_fixture("gl016", ["GL016"])
    lines = {f.line for f in findings}
    # reduce_model (bound via shard_map) and the suppressed twin are quiet
    assert len(findings) == 1 and all(
        "reduce_model" not in f.message for f in findings
    )
    assert lines != set()


def test_gl012_vmap_bound_axis_not_a_typo():
    """The GL016 substrate refines GL012: an axis bound by a reachable
    vmap(axis_name=) is legitimate even though mesh.py never declares
    it (mapper.py's 'rollout' lane axis)."""
    findings = _lint_fixture("gl016", ["GL012"],
                             only="cst_captioning_tpu/mapper.py")
    assert findings == []


def test_gl016_unbound_helper_called_from_plain_context(tmp_path):
    """A helper with a literal mesh-axis collective whose only caller is
    an ordinary function (no binding anywhere) IS a finding — that is
    the runtime unbound-axis error."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def helper(x):\n"
        "    return jax.lax.psum(x, 'data')\n"
        "def epoch(xs):\n"
        "    return [helper(x) for x in xs]\n"
    ), rules=["GL016"])
    assert _rules_of(findings) == ["GL016"]
    assert findings[0].line == 3


def test_gl016_shard_map_without_axis_names_binds_all_mesh_axes(tmp_path):
    """A shard_map with no axis_names= literal binds every declared mesh
    axis (the mesh argument is dynamic): collectives over any declared
    axis under it stay quiet."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def helper(x):\n"
        "    return jax.lax.psum(x, 'seq')\n"
        "def run(mesh, xs):\n"
        "    def body(x):\n"
        "        return helper(x)\n"
        "    return shard_map(body, mesh=mesh, in_specs=None,\n"
        "                     out_specs=None)(xs)\n"
    ), rules=["GL016"])
    assert findings == []


def test_gl016_string_default_axis_param_unbound_is_finding(tmp_path):
    """The ``axis="data"`` factory spelling: an axis routed through a
    string-default parameter resolves like a literal, so a helper whose
    only caller is an ordinary function IS a finding — this is the
    carry-over GL016 previously could not see."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def helper(x, axis='data'):\n"
        "    return jax.lax.psum(x, axis)\n"
        "def epoch(xs):\n"
        "    return [helper(x) for x in xs]\n"
    ), rules=["GL016"])
    assert _rules_of(findings) == ["GL016"]
    assert findings[0].line == 3 and "'data'" in findings[0].message


def test_gl016_string_default_axis_inherited_by_nested_def(tmp_path):
    """The make_*_step closure spelling: the nested device fn inherits
    the factory's ``axis="data"`` default; bound via shard_map the
    collective stays quiet, called plainly it is a finding."""
    src = (
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def make_step(mesh, axis='data'):\n"
        "    def device_step(x):\n"
        "        return jax.lax.psum(x, axis)\n"
        "    return shard_map(device_step, mesh=mesh,\n"
        "                     in_specs=None, out_specs=None)\n"
    )
    assert _lint(tmp_path / "bound", "cst_captioning_tpu/mod.py", src,
                 rules=["GL016"]) == []
    plain = src.replace(
        "    return shard_map(device_step, mesh=mesh,\n"
        "                     in_specs=None, out_specs=None)\n",
        "    return device_step(0)\n"
        "def epoch(mesh, xs):\n"
        "    return [make_step(mesh) for x in xs]\n",
    )
    findings = _lint(tmp_path / "plain", "cst_captioning_tpu/mod.py",
                     plain, rules=["GL016"])
    assert _rules_of(findings) == ["GL016"]
    assert findings[0].line == 5


def test_gl016_empty_string_axis_default_resolves_to_nothing(tmp_path):
    """The SP factories spell ``data_axis: str = ""`` for "no data
    axis"; an empty default must NOT be recorded as an axis (and the
    call site stays unresolvable, hence quiet)."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def helper(x, data_axis=''):\n"
        "    if data_axis:\n"
        "        return jax.lax.psum(x, data_axis)\n"
        "    return x\n"
        "def epoch(xs):\n"
        "    return [helper(x) for x in xs]\n"
    ), rules=["GL016"])
    assert findings == []


def test_gl016_reassigned_axis_param_drops_out_of_env(tmp_path):
    """A rebind of the string-default parameter makes it unresolvable
    again — never guess the default still holds."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def helper(x, axis='data'):\n"
        "    axis = pick_axis(x)\n"
        "    return jax.lax.psum(x, axis)\n"
        "def pick_axis(x):\n"
        "    return 'seq'\n"
        "def epoch(xs):\n"
        "    return [helper(x) for x in xs]\n"
    ), rules=["GL016"])
    assert findings == []


# ---- GL017: interprocedural donation hazards --------------------------------

def test_gl017_cross_file_donation_hazards():
    """The acceptance trio: use-after-donate through the make_step
    factory, the loop-carried un-rebound donation, and the outer jit()
    that silently drops a wrapper's donation — all facts living in
    steps_lib.py."""
    findings = _lint_fixture("gl017", ["GL017"])
    findings = [f for f in findings if f.path.endswith("loop.py")]
    assert len(findings) == 3
    factory, loop, wrapper = sorted(findings, key=lambda f: f.line)
    assert factory.severity == "error"
    assert "donated" in factory.message and "make_step" in factory.message
    assert "fused_update" in loop.message
    assert wrapper.severity == "warning"
    assert "local_wrapper" in wrapper.message
    assert "ignored" in wrapper.message


def test_gl017_single_file_engine_provably_cannot():
    assert _lint_fixture(
        "gl017", ["GL017"], only="cst_captioning_tpu/loop.py"
    ) == []


def test_gl017_rebind_and_read_before_and_suppressed_quiet():
    findings = _lint_fixture("gl017", ["GL017"])
    for f in findings:
        assert "good_rebind" not in f.context
        assert "good_read_before" not in f.context
    # the suppressed twin is the same shape as the factory positive;
    # ring.py contributes exactly its one attribute-rooted positive
    assert len(findings) == 4


def test_gl017_attribute_rooted_donation():
    """``self._buf`` donated through ``self._write`` (an attribute-rooted
    method resolved via the index) flags when re-read un-rebound; the
    donate-and-rebind ring idiom and a read-before stay clean."""
    findings = _lint_fixture("gl017", ["GL017"])
    ring = [f for f in findings if f.path.endswith("ring.py")]
    assert len(ring) == 1
    assert "self._buf" in ring[0].message
    assert "_write" in ring[0].message
    assert "self._buf.shape" in ring[0].context
    for f in findings:
        assert "good_push" not in f.context
        assert "good_read_first" not in f.context


def test_gl017_local_jit_use_after_donate(tmp_path):
    """Single-file form: a locally-built donating jit, buffer re-read
    after the donating call."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def train(state, batch, impl):\n"
        "    step = jax.jit(impl, donate_argnums=(0,))\n"
        "    new_state = step(state, batch)\n"
        "    return new_state, state.loss\n"
    ), rules=["GL017"])
    assert _rules_of(findings) == ["GL017"]
    assert findings[0].line == 5


def test_gl017_dynamic_donation_stays_out_of_scope(tmp_path):
    """`donate_argnums=(0,) if donate else ()` is dynamic: no fact is
    recorded, nothing fires (never guess) — the repo's steps.py
    factories keep linting clean."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def make(impl, donate):\n"
        "    return jax.jit(impl, donate_argnums=(0,) if donate else ())\n"
        "def train(state, batch, impl, donate):\n"
        "    step = make(impl, donate)\n"
        "    new_state = step(state, batch)\n"
        "    return new_state, state.loss\n"
    ), rules=["GL017"])
    assert findings == []


def test_gl017_branch_exclusive_donation_no_false_positive(tmp_path):
    """A donation in one `if` arm must not flag a read in the OTHER arm
    (exclusive paths); a read AFTER the join on the donating path is
    still caught via the may-join."""
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def train(state, batch, impl, fast):\n"
        "    step = jax.jit(impl, donate_argnums=(0,))\n"
        "    if fast:\n"
        "        out = step(state, batch)\n"
        "    else:\n"
        "        out = state.replace(step=state.step + 1)\n"
        "    return out\n"
    ), rules=["GL017"])
    assert findings == []
    findings = _lint(tmp_path, "cst_captioning_tpu/mod.py", (
        "import jax\n"
        "def train(state, batch, impl, fast):\n"
        "    step = jax.jit(impl, donate_argnums=(0,))\n"
        "    if fast:\n"
        "        out = step(state, batch)\n"
        "    else:\n"
        "        out = None\n"
        "    return out, state.loss\n"
    ), rules=["GL017"])
    assert _rules_of(findings) == ["GL017"] and findings[0].line == 8


def test_gl017_not_applied_in_tests(tmp_path):
    findings = _lint(tmp_path, "tests/test_fake.py", (
        "import jax\n"
        "def test_donation_error(state, batch, impl):\n"
        "    step = jax.jit(impl, donate_argnums=(0,))\n"
        "    new_state = step(state, batch)\n"
        "    return new_state, state.loss\n"
    ), rules=["GL017"])
    assert findings == []


# ---- autofix engine ---------------------------------------------------------

def _write_repo(tmp_path, files):
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
    (tmp_path / "graftlint.baseline").write_text(
        json.dumps({"version": 1, "entries": []})
    )


_FIXABLE_GL013 = {
    "cst_captioning_tpu/producer.py": (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "@jax.jit\n"
        "def encode(x):\n"
        "    return jnp.tanh(x)\n"
        "def decode(feats):\n"
        "    return encode(feats) * 2\n"
    ),
    "cst_captioning_tpu/consumer.py": (
        "import jax\n"
        "import numpy as np\n"
        "from cst_captioning_tpu.producer import decode\n"
        "def to_host(feats):\n"
        "    tokens = decode(feats)\n"
        "    return np.asarray(tokens)\n"
    ),
}


def test_fix_applies_and_is_idempotent(tmp_path, capsys):
    """--fix rewrites np.asarray -> jax.device_get, the tree relints
    clean, and a second --fix is a byte-for-byte no-op (the pinned
    idempotence contract)."""
    _write_repo(tmp_path, _FIXABLE_GL013)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix"]) == 0
    capsys.readouterr()
    fixed = (tmp_path / "cst_captioning_tpu/consumer.py").read_text()
    assert "jax.device_get(tokens)" in fixed and "np.asarray" not in fixed
    assert cli_main(args) == 0  # tree is lint-clean after the fix
    before = fixed
    assert cli_main(args + ["--fix"]) == 0
    assert (tmp_path / "cst_captioning_tpu/consumer.py").read_text() == before


_FIXABLE_GL013_NO_JAX = {
    "cst_captioning_tpu/producer.py":
        _FIXABLE_GL013["cst_captioning_tpu/producer.py"],
    # no `import jax` anywhere — the fix must insert it (once, despite
    # two findings wanting it)
    "cst_captioning_tpu/consumer.py": (
        "import numpy as np\n"
        "from cst_captioning_tpu.producer import decode\n"
        "def to_host(feats):\n"
        "    tokens = decode(feats)\n"
        "    return np.asarray(tokens)\n"
        "def to_host_twice(feats):\n"
        "    tokens = decode(feats)\n"
        "    return np.asarray(tokens)\n"
    ),
}


def test_fix_inserts_missing_jax_import(tmp_path, capsys):
    """A consumer with NO jax import still gets the mechanical rewrite:
    --fix inserts ``import jax`` exactly once (grouped onto the first
    import), rewrites BOTH sinks, relints clean, and stays idempotent."""
    _write_repo(tmp_path, _FIXABLE_GL013_NO_JAX)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix"]) == 0
    capsys.readouterr()
    fixed = (tmp_path / "cst_captioning_tpu/consumer.py").read_text()
    lines = fixed.splitlines()
    assert lines[0] == "import jax" and lines[1] == "import numpy as np"
    assert fixed.count("import jax\n") == 1
    assert fixed.count("jax.device_get(tokens)") == 2
    assert "np.asarray" not in fixed
    assert cli_main(args) == 0  # tree is lint-clean after the fix
    before = fixed
    assert cli_main(args + ["--fix"]) == 0
    assert (tmp_path / "cst_captioning_tpu/consumer.py").read_text() == before


def test_fix_import_insertion_respects_future_imports(tmp_path, capsys):
    """``from __future__ import ...`` must stay first in the file: the
    inserted ``import jax`` lands after the last future import (and
    after the module docstring)."""
    files = dict(_FIXABLE_GL013_NO_JAX)
    files["cst_captioning_tpu/consumer.py"] = (
        '"""Reads captions back to host."""\n'
        "from __future__ import annotations\n"
        "import numpy as np\n"
        "from cst_captioning_tpu.producer import decode\n"
        "def to_host(feats):\n"
        "    tokens = decode(feats)\n"
        "    return np.asarray(tokens)\n"
    )
    _write_repo(tmp_path, files)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix"]) == 0
    capsys.readouterr()
    lines = (
        tmp_path / "cst_captioning_tpu/consumer.py"
    ).read_text().splitlines()
    assert lines[1] == "from __future__ import annotations"
    assert lines[2] == "import jax"
    assert cli_main(args) == 0
    assert cli_main(args + ["--fix"]) == 0  # idempotent


def test_fix_dry_run_prints_diff_and_writes_nothing(tmp_path, capsys):
    _write_repo(tmp_path, _FIXABLE_GL013)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache", "--fix", "--dry-run"]
    assert cli_main(args) == 0
    out = capsys.readouterr()
    assert "+    return jax.device_get(tokens)" in out.out
    assert "-    return np.asarray(tokens)" in out.out
    assert "would fix" in out.err
    src = (tmp_path / "cst_captioning_tpu/consumer.py").read_text()
    assert "np.asarray(tokens)" in src  # untouched


def test_fix_check_gates_until_fixed(tmp_path, capsys):
    """--fix-check is the CI spelling: exit 1 while an autofixable
    finding is unfixed, 0 after --fix; it never writes."""
    _write_repo(tmp_path, _FIXABLE_GL013)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix-check"]) == 1
    err = capsys.readouterr().err
    assert "autofixable" in err and "--fix" in err
    src = (tmp_path / "cst_captioning_tpu/consumer.py").read_text()
    assert "np.asarray(tokens)" in src
    assert cli_main(args + ["--fix"]) == 0
    capsys.readouterr()
    assert cli_main(args + ["--fix-check"]) == 0


def test_fix_and_fix_check_are_exclusive(tmp_path, capsys):
    _write_repo(tmp_path, {})
    assert cli_main([str(tmp_path), "--root", str(tmp_path), "--fix",
                     "--fix-check"]) == 2
    assert cli_main([str(tmp_path), "--root", str(tmp_path),
                     "--dry-run"]) == 2


def test_fix_removes_stale_suppressions_and_baseline(tmp_path, capsys):
    """The two repair classes --check-stale only reports: a dead inline
    disable= comment is removed (whole line when alone, trimmed when
    sharing one) and a dead baseline entry is dropped from the file."""
    _write_repo(tmp_path, {
        "cst_captioning_tpu/mod.py": (
            "def f(x):\n"
            "    return x  # graftlint: disable=GL001 (long fixed)\n"
            "def g(x):\n"
            "    # graftlint: disable-next-line=GL003\n"
            "    return x\n"
        ),
    })
    (tmp_path / "graftlint.baseline").write_text(json.dumps({
        "version": 1,
        "entries": [{
            "rule": "GL001", "path": "cst_captioning_tpu/mod.py",
            "context": "return np.asarray(ghost)", "count": 1,
            "reason": "the code site was fixed long ago",
        }],
    }))
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--check-stale"]) == 1  # stale gates
    capsys.readouterr()
    assert cli_main(args + ["--fix"]) == 0
    src = (tmp_path / "cst_captioning_tpu/mod.py").read_text()
    assert "graftlint" not in src
    assert "return x" in src  # the code lines survived
    bl = json.loads((tmp_path / "graftlint.baseline").read_text())
    assert bl["entries"] == []
    capsys.readouterr()
    assert cli_main(args + ["--check-stale"]) == 0  # now stale-clean


def test_fix_trims_one_dead_id_from_shared_suppression(tmp_path, capsys):
    """A comment disabling two rules where only one still fires keeps the
    live id."""
    _write_repo(tmp_path, {
        "cst_captioning_tpu/train/mod.py": (
            "import jax\n"
            "import numpy as np\n"
            "@jax.jit\n"
            "def step(x):\n"
            "    return np.asarray(x)  # graftlint: disable=GL001,GL003\n"
        ),
    })
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix"]) == 0
    src = (tmp_path / "cst_captioning_tpu/train/mod.py").read_text()
    assert "disable=GL001" in src and "GL003" not in src


def test_overlapping_edits_refused():
    """Two fixes claiming the same span: the engine applies the first and
    refuses the second — never merges."""
    from cst_captioning_tpu.tools.graftlint.core import Edit
    from cst_captioning_tpu.tools.graftlint.fixes import (
        OverlappingEditsError,
        apply_edits,
        edits_overlap,
    )

    src = "a = np.asarray(x)\n"
    e1 = Edit(line=1, col=4, end_line=1, end_col=14, replacement="jd")
    e2 = Edit(line=1, col=4, end_line=1, end_col=14, replacement="other")
    e3 = Edit(line=1, col=15, end_line=1, end_col=16, replacement="y")
    with pytest.raises(OverlappingEditsError):
        apply_edits(src, [e1, e2])
    assert edits_overlap(src, [e1], [e2])
    assert not edits_overlap(src, [e1], [e3])
    assert apply_edits(src, [e1, e3]) == "a = jd(y)\n"


def test_fix_gl011_carry_init_dtype(tmp_path, capsys):
    _write_repo(tmp_path, {
        "cst_captioning_tpu/mod.py": (
            "import jax\n"
            "import jax.numpy as jnp\n"
            "def outer(xs):\n"
            "    def body(c, x):\n"
            "        return (c + x).astype(jnp.bfloat16), x\n"
            "    init = jnp.zeros((4,), jnp.float32)\n"
            "    return jax.lax.scan(body, init, xs)\n"
        ),
    })
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache", "--fix"]
    assert cli_main(args) == 0
    src = (tmp_path / "cst_captioning_tpu/mod.py").read_text()
    assert "init = jnp.zeros((4,), jnp.bfloat16)" in src


def test_fix_gl005_routes_through_dtype_param(tmp_path, capsys):
    _write_repo(tmp_path, {
        "cst_captioning_tpu/models/mod.py": (
            "import jax.numpy as jnp\n"
            "def forward(x, dtype):\n"
            "    bias = jnp.zeros((4,), jnp.float32)\n"
            "    return x + bias\n"
        ),
    })
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache", "--fix"]
    assert cli_main(args) == 0
    src = (tmp_path / "cst_captioning_tpu/models/mod.py").read_text()
    assert "bias = jnp.zeros((4,), dtype)" in src


def test_fix_gl005_no_dtype_param_stays_manual(tmp_path, capsys):
    """Without a dtype in scope there is no mechanical spelling: the
    finding still gates, but --fix-check does not claim it."""
    _write_repo(tmp_path, {
        "cst_captioning_tpu/models/mod.py": (
            "import jax.numpy as jnp\n"
            "def forward(x):\n"
            "    bias = jnp.zeros((4,), jnp.float32)\n"
            "    return x + bias\n"
        ),
    })
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--fix-check"]) == 1  # GL005 still gates...
    err = capsys.readouterr().err
    assert "autofixable" not in err  # ...but not as an unfixed autofix


def test_fix_skips_baselined_findings(tmp_path, capsys):
    """Baselined findings are intentional: --fix must not rewrite them."""
    _write_repo(tmp_path, _FIXABLE_GL013)
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache"]
    assert cli_main(args + ["--write-baseline"]) == 0
    capsys.readouterr()
    assert cli_main(args + ["--fix"]) == 0
    src = (tmp_path / "cst_captioning_tpu/consumer.py").read_text()
    assert "np.asarray(tokens)" in src  # untouched: grandfathered


def test_json_fixes_block_counts_autofixable(tmp_path, capsys):
    _write_repo(tmp_path, _FIXABLE_GL013)
    rc = cli_main([str(tmp_path / "cst_captioning_tpu"), "--root",
                   str(tmp_path), "--no-cache", "--json"])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1
    assert report["fixes"]["autofixable"] == 1
    assert report["fixes"]["by_rule"] == {"GL013": 1}
    fixable = [f for f in report["findings"] if f["fix"]]
    assert len(fixable) == 1
    fix = fixable[0]["fix"]
    assert "device_get" in fix["description"]
    assert all(
        set(e) == {"line", "col", "end_line", "end_col", "replacement"}
        for e in fix["edits"]
    )


# ---- summary cache: v3 schema (axis + donation summaries) -------------------

def test_cache_schema_bump_cold_starts_cleanly(tmp_path):
    """A cache written by an OLDER schema version is discarded wholesale:
    the build re-summarizes everything and still computes the new axis/
    donation facts (no half-read of the old schema)."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    mod = tmp_path / "m.py"
    mod.write_text(
        "import functools\n"
        "import jax\n"
        "@functools.partial(jax.jit, donate_argnums=(0,))\n"
        "def update(state, batch):\n"
        "    return state\n"
    )
    cache = tmp_path / "cache.json"
    cache.write_text(json.dumps({
        "version": 2,  # the pre-axis/donation schema
        "files": {"m.py": {"mtime": 0.0, "size": 0,
                           "summary": {"bogus": "shape"}}},
    }))
    idx = ProjectIndex.build([str(mod)], str(tmp_path),
                             cache_path=str(cache))
    assert idx.stats.summarized == 1 and idx.stats.cached == 0
    assert idx.functions["m.update"].donated_argnums == [0]
    # the rewritten cache carries the current schema version and
    # round-trips the new fields
    from cst_captioning_tpu.tools.graftlint.project import _CACHE_VERSION
    data = json.loads(cache.read_text())
    assert data["version"] == _CACHE_VERSION
    idx2 = ProjectIndex.build([str(mod)], str(tmp_path),
                              cache_path=str(cache))
    assert idx2.stats.cached == 1
    assert idx2.functions["m.update"].donated_argnums == [0]


def test_cache_round_trips_axis_and_donation_summaries(tmp_path):
    """Warm-cache builds must serve the NEW summary fields (axis tables,
    donation facts) identically to a cold build — the fields are part of
    the cached schema, not recomputed."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    (tmp_path / "lib.py").write_text(
        "import jax\n"
        "def helper(x):\n"
        "    return jax.lax.psum(x, 'data')\n"
        "def make_step(impl):\n"
        "    return jax.jit(impl, donate_argnums=(1,))\n"
    )
    (tmp_path / "use.py").write_text(
        "import jax\n"
        "from lib import helper\n"
        "def run(xs):\n"
        "    return jax.vmap(helper, axis_name='data')(xs)\n"
    )
    files = [str(tmp_path / "lib.py"), str(tmp_path / "use.py")]
    cache = tmp_path / "cache.json"
    cold = ProjectIndex.build(files, str(tmp_path), cache_path=str(cache))
    warm = ProjectIndex.build(files, str(tmp_path), cache_path=str(cache))
    assert warm.stats.cached == 2 and warm.stats.summarized == 0
    for idx in (cold, warm):
        assert idx.functions["lib.make_step"].returns_donating == [1]
        env, has_ctx = idx.axis_env_of("lib", "helper")
        assert has_ctx and "data" in env
        info = idx.modules["lib"].axis_funcs["helper"]
        assert info.collectives == [("psum", "data", 3, 11)]


def test_axis_env_transitive_through_helper_chain(tmp_path):
    """Axis environments propagate through ordinary call edges: bound
    body -> helper -> leaf, the leaf inherits the binding two hops up."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    (tmp_path / "m.py").write_text(
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def leaf(x):\n"
        "    return jax.lax.psum(x, 'data')\n"
        "def mid(x):\n"
        "    return leaf(x)\n"
        "def run(mesh, xs):\n"
        "    def body(x):\n"
        "        return mid(x)\n"
        "    return shard_map(body, mesh=mesh, in_specs=None,\n"
        "                     out_specs=None, axis_names=('data',))(xs)\n"
    )
    idx = ProjectIndex.build([str(tmp_path / "m.py")], str(tmp_path),
                             cache_path="")
    for qual in ("leaf", "mid", "run.body"):
        env, has_ctx = idx.axis_env_of("m", qual)
        assert has_ctx and env == frozenset({"data"}), qual

# ---- GL018: partition-rule table coverage & shadowing -----------------------

def test_gl018_shadowed_no_match_and_uncovered():
    """THE acceptance fixture: a non-canonical regex rule table with a
    fully-shadowed dead row (autofixable), a rule matching no contract
    param, and a contract param matched by no rule — three findings; the
    suppressed twin and the dynamically-built table stay quiet."""
    findings = _lint_fixture("gl018", ["GL018"])
    assert _rules_of(findings) == ["GL018"]
    assert all(f.path.endswith("bucket_rules.py") for f in findings)
    by_line = {f.line: f for f in findings}
    assert set(by_line) == {17, 20, 21}
    # uncovered contract param anchors to the table header
    assert "params/head/w" in by_line[17].message
    assert by_line[17].fix is None
    # dead row: every param it matches is claimed earlier — autofix
    # deletes it (provably behavior-identical under first-match-wins)
    assert "dec_again" in by_line[20].message
    assert "shadowed" in by_line[20].message
    assert by_line[20].fix is not None
    # rule whose family was renamed away: matches nothing
    assert "lstm_gate" in by_line[21].message
    assert by_line[21].fix is None
    for f in findings:
        assert f.severity == "error"


def test_gl018_dynamic_table_provably_cannot():
    """A table built by a comprehension carries no literal (family,
    regex) rows: single-file analysis provably cannot check it, so the
    rule stays quiet rather than guess."""
    assert _lint_fixture(
        "gl018", ["GL018"],
        only="cst_captioning_tpu/parallel/dynamic_rules.py",
    ) == []


def test_gl018_canonical_table_shadowing_only(tmp_path):
    """GL007 owns coverage for the canonical PARAM_PARTITION_RULES —
    GL018 adds only the shadowing check there (no duplicate no-match /
    uncovered findings)."""
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "shardings_contract.json").write_text(
        json.dumps({"params": ["params/enc/w", "params/dec/w",
                               "params/orphan/w"]})
    )
    findings = _lint(tmp_path, "cst_captioning_tpu/train/mesh.py", (
        "PARAM_PARTITION_RULES = (\n"
        "    ('enc', r'params/enc/.*', ()),\n"
        "    ('enc_dup', r'params/enc/w', ()),\n"   # shadowed -> GL018
        "    ('no_match', r'params/gone/.*', ()),\n"  # GL007's job, not ours
        ")\n"
    ), rules=["GL018"])
    assert len(findings) == 1
    assert findings[0].line == 3 and "enc_dup" in findings[0].message
    # params/orphan/w is uncovered, but coverage of the canonical table
    # belongs to GL007 — GL018 must not double-report it
    assert all("orphan" not in f.message for f in findings)


def test_gl018_covers_mp_table_next_to_canonical(tmp_path):
    """The flagship-XL layout: MP_PARAM_PARTITION_RULES lives beside the
    canonical table in the same module. GL018 applies the FULL check there
    (coverage + shadowing), so a dead mp row and an mp rule matching no
    contract param are both findings while the canonical twin stays
    GL007's job."""
    (tmp_path / "scripts").mkdir()
    (tmp_path / "scripts" / "shardings_contract.json").write_text(
        json.dumps({"params": ["params/enc/w", "params/dec/w"]})
    )
    findings = _lint(tmp_path, "cst_captioning_tpu/train/mesh.py", (
        "PARAM_PARTITION_RULES = (\n"
        "    ('enc', r'params/enc/.*', ()),\n"
        "    ('dec', r'params/dec/.*', ()),\n"
        ")\n"
        "MP_PARAM_PARTITION_RULES = (\n"
        "    ('enc', r'params/enc/.*', ()),\n"
        "    ('dec', r'params/dec/.*', ()),\n"
        "    ('dec_dead', r'params/dec/w', ()),\n"     # shadowed by 'dec'
        "    ('gate_gone', r'params/gate/.*', ()),\n"  # matches nothing
        ")\n"
    ), rules=["GL018"])
    assert _rules_of(findings) == ["GL018"]
    msgs = {f.line: f for f in findings}
    assert any("dec_dead" in f.message and "shadowed" in f.message
               and f.fix is not None for f in findings)
    assert any("gate_gone" in f.message for f in findings)
    assert all("MP_PARAM_PARTITION_RULES" in f.message for f in findings)
    assert len(msgs) == 2


def _mp_mesh_fixture(tmp_path):
    """A fixture train/mesh.py declaring the flagship-XL axes the way the
    real one does — string defaults of *axis params (the scrape's input)."""
    (tmp_path / "cst_captioning_tpu" / "train").mkdir(parents=True)
    (tmp_path / "cst_captioning_tpu" / "train" / "mesh.py").write_text(
        "def make_mesh(num_devices=0, axis='data', seq_devices=1,\n"
        "              seq_axis='seq', mp_devices=1, mp_axis='mp'):\n"
        "    return None\n"
    )


def test_gl015_learns_mp_axis_from_mesh_scrape(tmp_path):
    """P('data', 'mp') literals lint clean once make_mesh grows the
    mp_axis='mp' default — no rule-table edit, the axis scrape picks it
    up; an undeclared axis still fires and the allowed set names 'mp'."""
    _mp_mesh_fixture(tmp_path)
    (tmp_path / "cst_captioning_tpu" / "use.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "def f():\n"
        "    return P('data', 'mp'), P(None, 'mp')\n"
    )
    assert lint_paths([str(tmp_path)], str(tmp_path), rule_ids=["GL015"],
                      cache_path="").findings == []
    (tmp_path / "cst_captioning_tpu" / "use.py").write_text(
        "from jax.sharding import PartitionSpec as P\n"
        "def f():\n"
        "    return P('tp')\n"
    )
    findings = lint_paths([str(tmp_path)], str(tmp_path),
                          rule_ids=["GL015"], cache_path="").findings
    assert _rules_of(findings) == ["GL015"]
    assert "'tp'" in findings[0].message and "mp" in findings[0].message


def test_gl016_mp_axis_binding_via_shard_map(tmp_path):
    """A psum over 'mp' is quiet when every reachable caller binds it
    (shard_map axis_names including 'mp') and a finding from a plain
    calling context — same fixpoint as 'data'/'seq', new axis."""
    _mp_mesh_fixture(tmp_path)
    (tmp_path / "cst_captioning_tpu" / "merge.py").write_text(
        "import jax\n"
        "from jax.experimental.shard_map import shard_map\n"
        "def merge_lse(x):\n"
        "    return jax.lax.psum(x, 'mp')\n"
        "def run(mesh, xs):\n"
        "    def body(x):\n"
        "        return merge_lse(x)\n"
        "    return shard_map(body, mesh=mesh, in_specs=None,\n"
        "                     out_specs=None, axis_names=('data', 'mp'))(xs)\n"
    )
    assert lint_paths([str(tmp_path)], str(tmp_path), rule_ids=["GL016"],
                      cache_path="").findings == []
    (tmp_path / "cst_captioning_tpu" / "merge.py").write_text(
        "import jax\n"
        "def merge_lse(x):\n"
        "    return jax.lax.psum(x, 'mp')\n"
        "def run(xs):\n"
        "    return [merge_lse(x) for x in xs]\n"
    )
    findings = lint_paths([str(tmp_path)], str(tmp_path),
                          rule_ids=["GL016"], cache_path="").findings
    assert _rules_of(findings) == ["GL016"]
    assert "'mp'" in findings[0].message


def test_gl018_fix_deletes_dead_rule_and_is_idempotent(tmp_path, capsys):
    """--fix removes the provably-dead shadowed row (whole line, trailing
    comma and all), the tree relints clean, and a second --fix is a
    byte-for-byte no-op."""
    _write_repo(tmp_path, {
        "scripts/shardings_contract.json": json.dumps(
            {"params": ["params/enc/w", "params/dec/w"]}
        ),
        "cst_captioning_tpu/parallel/bucket_rules.py": (
            "SHARDING_CONTRACT = 'scripts/shardings_contract.json'\n"
            "COMM_PARTITION_RULES = (\n"
            "    ('all', r'params/.*', ()),\n"
            "    ('dup', r'params/dec/.*', ()),\n"
            ")\n"
        ),
    })
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache", "--rules", "GL018"]
    assert cli_main(args + ["--fix"]) == 0
    capsys.readouterr()
    fixed = (
        tmp_path / "cst_captioning_tpu/parallel/bucket_rules.py"
    ).read_text()
    assert "dup" not in fixed and "('all', r'params/.*', ())," in fixed
    assert cli_main(args) == 0  # clean after the fix
    before = fixed
    assert cli_main(args + ["--fix"]) == 0
    assert (
        tmp_path / "cst_captioning_tpu/parallel/bucket_rules.py"
    ).read_text() == before


# ---- GL019: cross-host collective operand drift -----------------------------

def test_gl019_cross_file_drift():
    """THE acceptance fixture: per-host constructor shape, a
    process_index()-conditional shape, and a callee whose summary says
    returns_host_shape (plus a helper reached only through the seed
    module's call closure) all fire; the param-shaped, literal-shaped,
    and gather-lengths-then-pad negatives stay quiet."""
    findings = _lint_fixture("gl019", ["GL019"])
    assert _rules_of(findings) == ["GL019"]
    sites = {(os.path.basename(f.path), f.line) for f in findings}
    assert sites == {
        ("helpers.py", 18),     # reachability-only finding
        ("multihost.py", 24),   # len(jax.local_devices()) leading dim
        ("multihost.py", 32),   # branch-dependent shape
        ("multihost.py", 36),   # cross-module returns_host_shape fact
    }
    for f in findings:
        assert f.severity == "error"
        # every message names the canonical repair
        assert "process_allgather" in f.message
    by_site = {(os.path.basename(f.path), f.line): f for f in findings}
    assert "local_devices" in by_site[("multihost.py", 24)].message
    assert "branch" in by_site[("multihost.py", 32)].message
    assert "local_block" in by_site[("multihost.py", 36)].message


def test_gl019_single_file_provably_cannot():
    """Linting the helper module ALONE must find nothing: without the
    seed module in the index, nothing proves its psum is a cross-host
    rendezvous (the reachability closure is empty)."""
    assert _lint_fixture(
        "gl019", ["GL019"],
        only="cst_captioning_tpu/parallel/helpers.py",
    ) == []


def test_gl019_host_value_reduction_is_fine(tmp_path):
    """VALUE host-dependence is the point of a reduction — only shape /
    wire-dtype drift deadlocks. A psum OVER a per-host value with a
    host-invariant shape must stay quiet."""
    findings = _lint(tmp_path, "cst_captioning_tpu/train/multihost.py", (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "def count_devices():\n"
        "    n = float(jax.local_device_count())\n"
        "    return jax.lax.psum(jnp.float32(n), 'data')\n"
    ), rules=["GL019"])
    assert findings == []


# ---- GL020: Pallas kernel contract ------------------------------------------

def test_gl020_arity_divisibility_and_vmem():
    """THE acceptance fixture: index-map arity vs grid rank (error),
    block dim vs grid divisor without a pl.when guard (error), and a
    fully-resolvable VMEM estimate over the ~16 MiB budget (warning);
    the guarded twin and the suppressed twin stay quiet."""
    findings = _lint_fixture(
        "gl020", ["GL020"],
        only="cst_captioning_tpu/ops/toy_kernels.py",
    )
    assert _rules_of(findings) == ["GL020"]
    assert all(f.path.endswith("toy_kernels.py") for f in findings)
    by_line = {f.line: f for f in findings}
    assert set(by_line) == {35, 46, 76}
    assert "arity" in by_line[35].message or "argument" in by_line[35].message
    assert by_line[35].severity == "error"
    assert "block_k" in by_line[46].message
    assert "block_n" in by_line[46].message
    assert by_line[46].severity == "error"
    assert "VMEM" in by_line[76].message and "MiB" in by_line[76].message
    assert by_line[76].severity == "warning"


def test_gl020_prefetch_grid_spec_sites():
    """grid_spec= sites resolve through PrefetchScalarGridSpec/GridSpec:
    index-map arity must be grid rank + num_scalar_prefetch (the prefetch
    refs trail the grid indices), unblocked memory_space=ANY refs and DMA
    semaphores cost no VMEM, and the clean twins stay quiet."""
    findings = _lint_fixture(
        "gl020", ["GL020"],
        only="cst_captioning_tpu/ops/prefetch_kernels.py",
    )
    assert _rules_of(findings) == ["GL020"]
    assert [f.line for f in findings] == [63]
    assert "scalar-prefetch" in findings[0].message
    assert findings[0].severity == "error"


def test_gl020_opaque_site_provably_cannot():
    """grid through an attribute, in_specs through a helper call:
    single-file analysis provably cannot resolve either — quiet, never
    guess."""
    assert _lint_fixture(
        "gl020", ["GL020"],
        only="cst_captioning_tpu/ops/opaque_kernels.py",
    ) == []


# ---- cache: corruption, v5 fields, submesh scrape ---------------------------

def test_corrupt_cache_falls_back_to_cold(tmp_path):
    """A truncated / garbage cache file (the failure the atomic
    tmp-then-rename write prevents) must cold-start cleanly, then leave
    a valid cache behind."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex
    from cst_captioning_tpu.tools.graftlint.project import _CACHE_VERSION

    mod = tmp_path / "m.py"
    mod.write_text("def f():\n    return 1\n")
    cache = tmp_path / "cache.json"
    cache.write_text('{"version": 5, "files": {')  # torn mid-write
    idx = ProjectIndex.build([str(mod)], str(tmp_path),
                             cache_path=str(cache))
    assert idx.stats.summarized == 1 and idx.stats.cached == 0
    data = json.loads(cache.read_text())  # rewritten valid
    assert data["version"] == _CACHE_VERSION
    warm = ProjectIndex.build([str(mod)], str(tmp_path),
                              cache_path=str(cache))
    assert warm.stats.cached == 1 and warm.stats.summarized == 0


def test_cache_round_trips_shape_and_host_facts(tmp_path):
    """The v5 summary fields (literal dims, PartitionSpec bindings,
    host-shape provenance) must serve identically from a warm cache."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    (tmp_path / "lib.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import PartitionSpec as P\n"
        "def local_block():\n"
        "    return jnp.zeros((jax.local_device_count(), 128),\n"
        "                     jnp.float32)\n"
        "def buf():\n"
        "    x = jnp.zeros((8, 128), jnp.bfloat16)\n"
        "    spec = P('data', None)\n"
        "    return x\n"
    )
    cache = tmp_path / "cache.json"
    files = [str(tmp_path / "lib.py")]
    cold = ProjectIndex.build(files, str(tmp_path), cache_path=str(cache))
    warm = ProjectIndex.build(files, str(tmp_path), cache_path=str(cache))
    assert warm.stats.cached == 1 and warm.stats.summarized == 0
    for idx in (cold, warm):
        host = idx.functions["lib.local_block"]
        assert host.returns_host_shape
        assert "local_device_count" in host.host_shape_reason
        plain = idx.functions["lib.buf"]
        assert plain.array_dims["x"] == [8, 128]
        assert plain.pspec_vars["spec"] == ["data", None]
        assert plain.return_dims == [8, 128]
        assert not plain.returns_host_shape


def test_submesh_axes_merge_into_mesh_decl(tmp_path):
    """parallel/submesh.py axis declarations join the train/mesh.py
    scrape, so GL012 treats the actor/learner submesh axis as declared."""
    from cst_captioning_tpu.tools.graftlint import ProjectIndex

    mesh = tmp_path / "cst_captioning_tpu" / "train" / "mesh.py"
    mesh.parent.mkdir(parents=True)
    mesh.write_text("def make_mesh(axis='data'):\n    return axis\n")
    sub = tmp_path / "cst_captioning_tpu" / "parallel" / "submesh.py"
    sub.parent.mkdir(parents=True)
    sub.write_text(
        "def plan_submesh(mesh, rollout_axis='actor'):\n"
        "    return rollout_axis\n"
    )
    mod = tmp_path / "cst_captioning_tpu" / "mod.py"
    mod.write_text(
        "import jax\n"
        "def f(x):\n"
        "    return jax.lax.psum(x, 'actor')\n"
    )
    idx = ProjectIndex.build(
        [str(mesh), str(sub), str(mod)], str(tmp_path), cache_path="",
    )
    assert {"data", "actor"} <= set(idx.mesh.axes)
    result = lint_paths([str(mod)], str(tmp_path), rule_ids=["GL012"],
                        cache_path="")
    assert result.findings == []
    # contrast: without submesh.py the same axis IS a GL012 typo
    sub.unlink()
    result = lint_paths([str(mod)], str(tmp_path), rule_ids=["GL012"],
                        cache_path="")
    assert _rules_of(result.findings) == ["GL012"]


# ---- README drift pin -------------------------------------------------------

def test_readme_rule_table_tracks_registry():
    """Every registered rule id appears in README's Static analysis rule
    table, and every GLxxx the README mentions is a live registered rule
    (no retired ids lingering in the docs)."""
    import re

    readme = open(os.path.join(REPO, "README.md")).read()
    registered = set(all_rules())
    mentioned = set(re.findall(r"\bGL\d{3}\b", readme))
    missing = {
        rid for rid in registered
        if not re.search(rf"\*\*{rid}\b", readme)
    }
    assert not missing, f"rules missing from README table: {sorted(missing)}"
    retired = mentioned - registered
    assert not retired, f"README names unregistered rules: {sorted(retired)}"


# ---- --changed-only: the git-scoped fast path -------------------------------

def _git(tmp_path, *argv):
    subprocess.run(
        ["git", "-C", str(tmp_path), "-c", "user.email=ci@example.com",
         "-c", "user.name=ci", *argv],
        check=True, capture_output=True,
    )


def test_changed_only_scopes_pass_two_to_the_diff(tmp_path, capsys):
    """Pass 1 still indexes the whole tree, but findings come only from
    files git reports changed: a pre-existing finding in an UNTOUCHED
    file stays out of the fast path (the full-tree gate owns it)."""
    files = dict(_FIXABLE_GL013)  # consumer.py holds the GL013 finding
    _write_repo(tmp_path, files)
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    args = [str(tmp_path / "cst_captioning_tpu"), "--root", str(tmp_path),
            "--no-cache", "--changed-only"]
    # clean tree: nothing to lint, exit 0
    assert cli_main(args) == 0
    assert "no changed" in capsys.readouterr().err
    # touch ONLY the clean producer: consumer's finding must not gate
    # the fast path
    prod = tmp_path / "cst_captioning_tpu/producer.py"
    prod.write_text(prod.read_text() + "\n# tuning note\n")
    assert cli_main(args) == 0
    err = capsys.readouterr().err
    assert "1 file(s), 0 finding(s)" in err
    # now dirty the consumer too: its finding rides the fast path
    assert cli_main(args + ["--rules", "GL013"]) == 0  # not changed yet
    capsys.readouterr()
    cons = tmp_path / "cst_captioning_tpu/consumer.py"
    cons.write_text(cons.read_text() + "\n# touched\n")
    assert cli_main(args) == 1
    out = capsys.readouterr()
    assert "GL013" in out.out and "2 file(s)" in out.err


def test_changed_only_excludes_authoritative_gates(tmp_path, capsys):
    _write_repo(tmp_path, {})
    _git(tmp_path, "init", "-q")
    _git(tmp_path, "add", "-A")
    _git(tmp_path, "commit", "-qm", "seed")
    base = [str(tmp_path), "--root", str(tmp_path), "--changed-only"]
    for gate in ("--fix", "--fix-check", "--write-baseline"):
        assert cli_main(base + [gate]) == 2
        assert "exclusive" in capsys.readouterr().err
    assert cli_main(base + ["--check-stale"]) == 2


def test_changed_only_outside_git_is_a_usage_error(tmp_path, capsys):
    _write_repo(tmp_path, {"cst_captioning_tpu/m.py": "X = 1\n"})
    env = dict(os.environ, GIT_DIR=str(tmp_path / "nope" / ".git"),
               GIT_CEILING_DIRECTORIES=str(tmp_path))
    rc = subprocess.run(
        [sys.executable, "-m", "cst_captioning_tpu.tools.graftlint",
         "cst_captioning_tpu", "--root", str(tmp_path), "--changed-only"],
        cwd=REPO, env=env, capture_output=True, text=True,
    )
    assert rc.returncode == 2
    assert "git checkout" in rc.stderr
