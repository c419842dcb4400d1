"""BENCHMARK.json against the files it names and the contract's limits; and
the same rules over the manifests later PRs would make of it by adding cells
as files and entries (``tiny.GROWN``: a cell of job ``eval`` and another cell
of job ``cst``, both on ``tests/second_architecture``), with no entry that is
there edited. No test here takes the committed cells to be the only ones: a
later PR commits cells and may not edit this file."""

import copy
import json
import os
import re

import pytest

from benchmark import run as bench_run
from benchmark.tests import tiny

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(ROOT, "benchmark")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module", params=list(tiny.GROWN))
def manifest(request):
    return tiny.GROWN[request.param](tiny.manifest())


def _workload_file(cell: str) -> str:
    """``workloads/<cell>.json``; the made-up cell's lies beside its
    configuration under ``tests/``, where no run finds it."""
    for base in (os.path.join(BENCH, "workloads"), tiny.SECOND):
        path = os.path.join(base, cell + ".json")
        if os.path.exists(path):
            return path
    raise AssertionError(f"no workload file for {cell}")


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
        # only a file under ``tests/``, at tiny sizes itself and never run,
        # may bring ``params`` alone
        if entry["file"].startswith("benchmark/tests/"):
            assert set(config["tiny"]) == {"params"}
        else:
            assert set(tiny.TINY_KEYS) | {"params"} <= set(config["tiny"])
            assert set(config["tiny"]["checks"]) == set(config["checks"])
        with open(_workload_file(w["name"])) as f:
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
        assert callable(bench_run.reader_of(m["name"]).read)


@pytest.mark.parametrize("grown", [g for g in tiny.GROWN if g != "as_committed"])
def test_a_new_cell_is_files_and_entries(grown):
    """The proof the next ``model_config`` PR needs: a cell of an
    architecture that exists only as files, of job ``eval`` or of job
    ``cst``, comes in as one configuration entry, one workload entry and
    per-layer entries of its own, and every entry that was there stays as it
    was, letter for letter (the fixture above holds the result to every rule
    of this file). Each new cell is given the metrics that carry no list and
    its own, and none that lists other cells."""
    committed = tiny.manifest()
    made = tiny.GROWN[grown](copy.deepcopy(committed))
    for group, entries in committed.items():
        if group in ("configs", "workloads", "per_layer"):
            assert made[group][:len(entries)] == entries, group
            assert len(made[group]) > len(entries), group
        else:
            assert made[group] == entries, group
    listless = {m["name"] for m in committed["per_layer"]
                if "workloads" not in m}
    added = made["per_layer"][len(committed["per_layer"]):]
    for w in made["workloads"][len(committed["workloads"]):]:
        given = {m["name"] for m in bench_run.metrics_of(made, "per_layer",
                                                         w["name"])}
        assert given == listless | {m["name"] for m in added
                                    if w["name"] in m["workloads"]}
        assert given - listless, "the new cell reads something of its own"
        assert not {"decode_roofline", "update_roofline", "epoch_turnover_ms",
                    "reward_ms_per_step", "allreduce_ms_per_step"} & given


def test_the_rl_steps_metrics_say_where_they_exist(manifest):
    """Every entry that reads the RL step, the epoch turnover or the prefetch
    feed carries a list; the entries of the two accepted ``cst`` cells list
    both, and whatever an entry lists is a cell of job ``cst`` (by its
    workload file). Cells that later PRs commit, of any job, leave this
    true: they are in no list that was there."""
    jobs = {}
    for w in manifest["workloads"]:
        with open(_workload_file(w["name"])) as f:
            jobs[w["name"]] = json.load(f)["job"]
    names = {m["name"] for m in manifest["per_layer"]}
    for m in manifest["per_layer"]:
        if re.match(r"(decode|update|reward|epoch)_|h2d_|prefetch_|allreduce_",
                    m["name"]):
            assert m.get("workloads"), m["name"]
            assert {jobs[c] for c in m["workloads"]} == {"cst"}, m["name"]
            if "." not in m["name"] and not m["name"].startswith("allreduce_"):
                assert set(tiny.ACCEPTED_CST) <= set(m["workloads"]), m["name"]
    assert {"decode_roofline", "update_roofline", "epoch_turnover_ms"} <= names


def test_files_under_paths_are_named_from_the_allowed_characters():
    allowed = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for base, dirs, names in os.walk(BENCH):
        dirs[:] = [d for d in dirs if d not in (".cache", "__pycache__")]
        for n in names:
            if n.endswith(".pyc"):
                continue
            rel = os.path.relpath(os.path.join(base, n), ROOT)
            assert allowed.match(rel), rel
