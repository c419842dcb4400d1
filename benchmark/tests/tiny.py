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
    """``config`` at its tiny sizes (a file that is tiny itself brings
    ``tiny.params`` alone and stays as it is)."""
    out = copy.deepcopy(config)
    tiny = out.pop("tiny")
    if not set(tiny) & set(TINY_KEYS):
        return out
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


# the two cells the benchmark was accepted with: what the RL step's entries
# list, whatever cells later PRs commit beside them
ACCEPTED_CST = ("msrvtt_attention.cst_b1792", "msrvtt_attention.cst_b1792_dp4")


def _with_the_second_architecture(manifest: dict, workload_file: str):
    """A copy of ``manifest`` with the second architecture's configuration
    entry and the cell of ``workload_file`` (beside its configuration under
    ``tests/``) appended; -> (the copy, the cell's name)."""
    out = copy.deepcopy(manifest)
    second = second_architecture()
    with open(os.path.join(SECOND, workload_file)) as f:
        cell = json.load(f)
    name = f"{cell['config']}.{cell['traffic']}"
    if second["name"] not in [c["name"] for c in out["configs"]]:
        out["configs"].append({
            "name": second["name"], "source": second["source"],
            "file": os.path.relpath(os.path.join(SECOND, "config.json"), ROOT),
            "reduced": second["reduced"],
            "why": "the rehearsal's second architecture"})
    out["workloads"].append(
        {k: cell[k] for k in ("config", "traffic", "chips", "why")}
        | {"name": name})
    return out, name


def with_an_eval_cell(manifest: dict) -> dict:
    """``manifest`` as a later PR would leave it after adding a cell of job
    ``eval`` for the second architecture: one configuration entry, one
    workload entry (its file: ``second_architecture/pooled_lstm.eval.json``)
    and the ``eval`` readers' entries, each listing the new cell. Nothing
    that is there is touched."""
    out, name = _with_the_second_architecture(manifest, "pooled_lstm.eval.json")
    there = {m["name"] for m in manifest["per_layer"]}
    for metric, unit, better, source, layer in (
            ("eval_decode_device_ms_per_step", "ms", "lower", "device_trace",
             "step programs"),
            ("eval_decode_roofline", "%", "higher", "device_trace",
             "step programs"),
            ("eval_score_ms_per_step", "ms", "lower", "program_span",
             "evaluation")):
        # once an ``eval`` cell is committed its entries are there, and the
        # next one's are entries of the same readers under names of their own
        out["per_layer"].append({
            "name": metric + (".pooled_lstm" if metric in there else ""),
            "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": "clips_per_s_per_chip",
            "workloads": [name]})
    return out


def with_a_cst_cell(manifest: dict) -> dict:
    """``manifest`` after a later PR has added another one-chip cell of job
    ``cst`` (the second architecture's; its file:
    ``second_architecture/pooled_lstm.cst.json``). The RL step's entries
    list the accepted cells and may not be edited, so the new cell brings
    each of them again under the name ``<metric>.<its own>``, listing itself:
    entries only, because ``run.reader_of`` reads ``<metric>.<anything>`` with
    ``layer_metrics/<metric>.py``."""
    out, name = _with_the_second_architecture(manifest, "pooled_lstm.cst.json")
    for m in manifest["per_layer"]:
        if set(ACCEPTED_CST) <= set(m.get("workloads", ())):
            out["per_layer"].append(
                {k: v for k, v in m.items() if k != "workloads"}
                | {"name": m["name"] + ".pooled_lstm", "workloads": [name]})
    return out


# the readers under ``layer_metrics/`` when these tests were written: what the
# rehearsal's walks and its stand-in traces feed. An entry that a later PR
# brings with a reader of its own (a kernel's roofline, say) is that PR's to
# test, in a file of its own
READERS = frozenset("""
    allreduce_ms_per_step caption_len_mean collate_ms_per_step
    compiles_in_window decode_device_ms_per_step decode_roofline
    decode_wait_ms_per_step device_idle_share epoch_drain_ms epoch_keys_ms
    epoch_readback_ms epoch_turnover_ms eval_decode_device_ms_per_step
    eval_decode_roofline eval_score_ms_per_step h2d_ms_per_step hbm_window_gib
    host_unattributed_ms_per_step input_wait_ms_per_step mfu_end_to_end
    peak_hbm_gib prefetch_wait_ms_per_step reward_ms_per_step
    reward_score_ms_per_step step_p50_ms update_device_ms_per_step
    update_roofline xe_device_ms_per_step xe_roofline""".split())


def assert_line_is_the_cells(got: dict, manifest: dict, cell: str) -> set:
    """The traced line ``got`` against what ``manifest`` gives ``cell``: no
    metric that it does not give the cell, and every one that it does and
    that a reader of :data:`READERS` reads (today: every one, so the line is
    exactly the cell's). -> the names the manifest gives the cell."""
    from benchmark import run as bench_run

    want = {m["name"] for m in bench_run.metrics_of(manifest, "per_layer", cell)}
    assert set(got) <= want, set(got) - want
    known = {n for n in want if n.split(".")[0] in READERS}
    assert known <= set(got), known - set(got)
    return want


GROWN = {"as_committed": lambda m: m, "with_an_eval_cell": with_an_eval_cell,
         "with_a_cst_cell": with_a_cst_cell,
         "with_both": lambda m: with_a_cst_cell(with_an_eval_cell(m))}


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
            f"{config['name']}-{workload.get('traffic', '')}-{workload['job']}"
            f"-{chips}-{int(trace)}")
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
