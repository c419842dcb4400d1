"""BENCHMARK.json against the files it names and the contract's limits."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _cells(m):
    return [w["name"] for w in m["workloads"]]


def test_keys_and_limits(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert manifest["paths"] == ["benchmark"]
    assert manifest["command"] == ["python3", "benchmark/run.py"]
    n = len(manifest["workloads"])
    assert 2 <= n <= 24 and 1 <= len(manifest["configs"]) <= 24
    # a full check with all 24 cells must fit the driver's 43200 s
    s = manifest["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert four <= max(n // 4, 1)
    assert len(json.dumps(manifest)) <= 64 * 1024


def test_names_units_and_whys(manifest):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in manifest[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
    assert len(set(names)) == len(names)
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    e2e = {m["name"] for m in manifest["end_to_end"]}
    assert "setup_s" in e2e
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES and m["moves"] in e2e
        assert 1 <= len(m["layer"]) <= 200 and "\n" not in m["layer"]
        layers.add(m["layer"])
        for cell in m.get("workloads", ()):
            assert cell in _cells(manifest)
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"


def test_every_name_resolves(manifest):
    """Every cell's configuration, workload file, job and metrics exist, and
    every cell reports setup_s, another end-to-end metric and a per-layer
    metric."""
    configs = {c["name"]: c for c in manifest["configs"]}
    files = [c["file"] for c in manifest["configs"]]
    assert len(set(files)) == len(files)
    used = set()
    for w in manifest["workloads"]:
        entry = configs[w["config"]]
        used.add(w["config"])
        assert entry["file"].startswith("benchmark/")
        with open(os.path.join(ROOT, entry["file"])) as f:
            config = json.load(f)
        assert config["name"] == w["config"]
        assert config["source"] == entry["source"]
        assert config["reduced"] == entry["reduced"]
        # what the configuration brings as files and blocks of its own
        for key in ("reference", "costs"):
            assert config[key].startswith("benchmark/"), key
            assert os.path.isfile(os.path.join(ROOT, config[key])), key
        assert all(e.get("reason") and "value" in e
                   for e in config["checks"].values())
        assert {"model", "overrides", "corpus", "policy", "checks",
                "params"} <= set(config["tiny"])
        assert set(config["tiny"]["checks"]) == set(config["checks"])
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            wl = json.load(f)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        for key in ("config", "traffic", "chips", "why"):
            assert wl[key] == w[key], (w["name"], key)
        assert os.path.exists(os.path.join(BENCH, "jobs", wl["job"] + ".py"))
        assert wl["job"] in config["tiny"]["params"]

        def of(group):
            return [m for m in manifest[group]
                    if "workloads" not in m or w["name"] in m["workloads"]]

        assert {"setup_s"} < {m["name"] for m in of("end_to_end")}
        assert of("per_layer")
    assert used == set(configs)
    for m in manifest["per_layer"]:
        reader = importlib.import_module("benchmark.layer_metrics." + m["name"])
        assert callable(reader.read)


def test_files_under_paths_are_named_from_the_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for n in names:
            if n.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(base, n), ROOT)
            assert allowed.match(rel), rel
