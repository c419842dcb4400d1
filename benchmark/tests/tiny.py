"""The CPU rehearsal's sizes and context. Every configuration file carries a
``tiny`` block: its own ``model``, ``overrides``, ``corpus``, ``policy`` and
``checks`` at sizes a CPU walks in seconds, and ``params`` for each job its
cells run. :func:`tiny_config` and :func:`tiny_workload` put them in the
file's and the workload's place; reference, cost model and every other key
stay the file's. Nothing here is ever measured."""

from __future__ import annotations

import copy
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
TINY_KEYS = ("model", "overrides", "corpus", "policy", "checks")


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config_file(name: str) -> dict:
    """The configuration ``name`` of the manifest, as its file has it."""
    entry = {c["name"]: c for c in manifest()["configs"]}[name]
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


def workload_file(cell: str) -> dict:
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        return json.load(f)


def tiny_config(config: dict) -> dict:
    out = copy.deepcopy(config)
    tiny = out.pop("tiny")
    out.update({k: tiny[k] for k in TINY_KEYS})
    out["name"] = "tiny_" + config["name"]
    return out


def tiny_workload(config: dict, job: str, workload: dict | None = None) -> dict:
    """The workload (a cell's file, or a bare one of ``job``) with the
    configuration's tiny ``params`` of that job."""
    out = copy.deepcopy(workload) if workload else {"job": job}
    out["params"] = copy.deepcopy(config["tiny"]["params"][job])
    return out


SECOND = os.path.join(HERE, "second_architecture")


def second_architecture() -> dict:
    """An architecture that exists only as files under ``tests/``: its
    configuration, its reference, its cost model, its tolerances."""
    with open(os.path.join(SECOND, "config.json")) as f:
        return json.load(f)


class Ctx:
    """What ``run.py``'s ``Run`` gives a job, without the device checks."""

    def __init__(self, workload, config, cache_dir, chips=1, seconds=1.5,
                 seed=0, trace=False):
        self.workload, self.config = workload, config
        self.seed, self.seconds, self.trace, self.chips = seed, seconds, trace, chips
        self.cache_dir = str(cache_dir)
        # a run directory of its own: the program's obs recorder is one per
        # process and keeps writing where the traced rehearsal pointed it
        self.run_dir = os.path.join(
            self.cache_dir, "run",
            f"{config['name']}-{workload['job']}-{chips}-{int(trace)}")
        os.makedirs(self.run_dir, exist_ok=True)
        self.obs_dir = os.path.join(self.run_dir, "obs")
        self.t_open = self.t_close = None
        self.lines: list[str] = []

    def log(self, msg):
        self.lines.append(msg)

    def window_opened(self, t):
        self.t_open = t

    def window_closed(self, t):
        self.t_close = t

    def step_listener(self, period):
        return None     # the rehearsal never starts the profiler

    def annotate(self, name):
        import contextlib

        return contextlib.nullcontext()
