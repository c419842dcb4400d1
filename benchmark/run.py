#!/usr/bin/env python3
"""One cell, once:

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything about a cell is data the harness finds by name: the cell in
``BENCHMARK.json``, its parameters in ``workloads/<cell>.json``, its sizes in
the configuration's file, the code that drives the program in
``jobs/<job>.py``, and each per-layer metric's reader in
``layer_metrics/<metric>.py`` (``reader_of``: a metric named
``<metric>.<anything>`` is the same quantity in other cells and has the same
reader). Adding a cell, a configuration, a job or a metric adds files; it
edits none (``README.md``).

Prints what it likes on the way (stderr) and, last on stdout, one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer metrics), ``device``, with
``--trace 1`` ``breakdown``, and last ``compared``: every number the run
compared, beside its limit (they are also the last lines on stderr). Exits
non-zero and prints no result without a TPU, with fewer chips than the cell
asks for, or without the program.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_DIR = os.path.join(HERE, ".cache")
COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
# the traced stretch of the window (``Stretch``): the trace starts this long
# after the window opens, and a stretch that has not seen its period of steps
# is cut this long after it began
TRACE_DELAY_S = 1.0
TRACE_SECONDS = 5.0
# what the profiler is told to collect, as attributes of ``ProfileOptions``
PROFILE_OPTIONS = {"python_tracer_level": 0}


def log(msg: str) -> None:
    print(f"[bench +{time.perf_counter() - T_PROCESS_START:7.2f}s] {msg}",
          file=sys.stderr, flush=True)


def load_cell(name: str) -> tuple[dict, dict, dict, dict]:
    """(manifest, the cell's entry, its workload file, its configuration)."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; "
                         f"there are {sorted(cells)}")
    cell = cells[name]
    with open(os.path.join(HERE, "workloads", name + ".json")) as f:
        workload = json.load(f)
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    with open(os.path.join(ROOT, cfg_entry["file"])) as f:
        config = json.load(f)
    for key in ("config", "traffic", "chips"):
        if workload[key] != cell[key]:
            raise SystemExit(f"workloads/{name}.json and BENCHMARK.json "
                             f"disagree on {key}")
    return manifest, cell, workload, config


def metrics_of(manifest: dict, group: str, cell: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or cell in m["workloads"]]


def reader_of(metric: str):
    """The module that reads a per-layer metric: ``layer_metrics/<the name up
    to its first dot>.py``. An entry may not be edited once it is there, so
    the cells a quantity is read in later come as entries of their own,
    ``decode_roofline.<what tells them apart>`` with their own ``workloads``
    list, and no file: the reader takes the program and its cost from what
    the cell's job hands over (``modules``, ``cost_shape``), not from a name."""
    return importlib.import_module(
        "benchmark.layer_metrics." + metric.split(".", 1)[0])


class Stretch:
    """The traced stretch of a ``--trace 1`` run, measured in work: one
    ``period`` of steps (an epoch), from whatever phase the trace starts in.

    The tracer thread starts the profiler ``delay_s`` after the window opens
    and takes the ``bench.sync`` mark; ``c0`` is the first step completion
    after the mark and the stretch is ``(c0, the period-th completion after
    it)``: exactly one epoch's steps and one turnover, so the device work in
    the trace does not grow as the host gets out of the device's way (the
    profiler's stop costs tens of seconds for every second a device was busy
    in the trace: ``PERF.md`` section 2).
    ``window`` is that interval, on ``time.perf_counter()``'s clock; the
    reduction and the per-step readers read over it. The trace stops early,
    said on stderr, when the window closes or ``cap_s`` after the stretch
    began (after the mark, while no step has completed); ``window`` is then
    ``(c0, the last completion seen)``, whole steps still, or from the mark to
    the stop when fewer than two steps completed."""

    def __init__(self, trace_dir: str, period: int, profiler=None,
                 delay_s: float = TRACE_DELAY_S, cap_s: float = TRACE_SECONDS):
        self.trace_dir, self.period = trace_dir, max(int(period), 1)
        self.delay_s, self.cap_s = delay_s, cap_s
        self._profiler = profiler
        self._cv = threading.Condition()
        self._done: list[float] = []    # step completions inside the window
        self._closed = False
        self._thread = threading.Thread(target=self._run, name="bench-tracer",
                                        daemon=True)
        self.sync_mark_perf: float | None = None
        self.window: tuple[float, float] | None = None
        self.steps = 0      # whole steps read: ``period`` unless cut short
        self.stop_trace_s: float | None = None
        self.error: str | None = None

    # -- called by the job's clock thread: record, wake, nothing else -------
    def step_completed(self, t: float) -> None:
        with self._cv:
            self._done.append(t)
            self._cv.notify()

    def window_closed(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify()

    def start(self) -> None:
        self._thread.start()

    def join(self) -> None:
        if self._thread.ident is not None:
            self._thread.join()

    def _run(self) -> None:
        try:
            prof = self._profiler
            if prof is None:
                import jax

                prof = jax.profiler
            with self._cv:
                if self._cv.wait_for(lambda: self._closed, self.delay_s):
                    raise RuntimeError("the window closed before the stretch "
                                       "began")
            opts = prof.ProfileOptions()
            for key, value in PROFILE_OPTIONS.items():
                setattr(opts, key, value)
            prof.start_trace(self.trace_dir, profiler_options=opts)
            try:
                self.sync_mark_perf = mark = time.perf_counter()
                with prof.TraceAnnotation("bench.sync"):
                    time.sleep(0.001)
                with self._cv:
                    while True:
                        # c0 and up to ``period`` completions after it
                        read = [t for t in self._done
                                if t > mark][:self.period + 1]
                        began = read[0] if read else mark
                        left = began + self.cap_s - time.perf_counter()
                        if len(read) > self.period or self._closed or left <= 0:
                            break
                        self._cv.wait(left)
                    closed = self._closed
                if len(read) > 1:
                    self.window, self.steps = (read[0], read[-1]), len(read) - 1
                else:
                    self.window = (mark, time.perf_counter())
                if self.steps < self.period:
                    log(f"THE TRACED STRETCH WAS CUT by "
                        f"{'the window closing' if closed else f'its cap of {self.cap_s} s'}"
                        f": {self.steps} of its period of {self.period} steps; "
                        "the per-layer readings are over what there is")
            finally:
                t0 = time.perf_counter()
                prof.stop_trace()
                self.stop_trace_s = time.perf_counter() - t0
        except Exception as e:  # reported; the per-layer trace metrics drop out
            self.error = f"{type(e).__name__}: {e}"


class Run:
    """What a job gets: the cell's data, the run's arguments, the places it
    may write, the window's two callbacks and, for a job that counts steps,
    the listener for their completions (``step_listener``)."""

    def __init__(self, cell, workload, config, seed, seconds, trace):
        self.cell, self.workload, self.config = cell, workload, config
        self.seed, self.seconds, self.trace = seed, seconds, bool(trace)
        self.chips = int(cell["chips"])
        self.cache_dir = CACHE_DIR
        # per-run scratch, overwritten by the next run of the cell
        self.run_dir = os.path.join(CACHE_DIR, "run", cell["name"])
        shutil.rmtree(self.run_dir, ignore_errors=True)
        os.makedirs(self.run_dir)
        self.obs_dir = os.path.join(self.run_dir, "obs")
        self.trace_dir = os.path.join(self.run_dir, "trace")
        self.log = log
        self.t_open = self.t_close = None
        self.compiles: list[tuple[float, float]] = []   # (perf_counter, secs)
        self.stretch: Stretch | None = None

    def step_listener(self, period: int):
        """What a job hands its ``StepClock`` as ``on_step``: in a traced run
        the stretch's listener, for a stretch of ``period`` steps (the steps
        of one epoch: the unit after which the job's work repeats); in an
        untraced run None, and nothing is installed."""
        if not self.trace:
            return None
        self.stretch = Stretch(self.trace_dir, period)
        return self.stretch.step_completed

    # -- called by the job's clock thread --------------------------------
    def window_opened(self, t: float) -> None:
        self.t_open = t
        log(f"window opened (set-up {t - T_PROCESS_START:.2f}s)")
        if self.stretch is not None:
            self.stretch.start()

    def window_closed(self, t: float) -> None:
        self.t_close = t
        log(f"window closed after {t - self.t_open:.3f}s")
        if self.stretch is not None:
            self.stretch.window_closed()

    def annotate(self, name: str):
        """A span of the benchmark's own in the profiler's trace."""
        import jax

        return jax.profiler.TraceAnnotation("bench." + name)

    def join_tracer(self) -> None:
        """Wait for the profiler's stop, and say what the stretch cost."""
        st = self.stretch
        if st is None:
            return
        st.join()
        if st.window is not None:
            log(f"traced stretch: {st.steps} steps (period {st.period}) in "
                f"{st.window[1] - st.window[0]:.3f}s, beginning "
                f"{st.window[0] - self.t_open:.3f}s into the window; "
                f"stop_trace() took {st.stop_trace_s:.2f}s")

    def compiles_in_window(self) -> list[float]:
        """Seconds of each compile (or compile-cache load) in the window."""
        return [s for t, s in self.compiles if self.t_open < t <= self.t_close]


def program_spans(obs_dir: str, wall_minus_perf: float) -> list[dict]:
    """The program's obs spans, on the benchmark's ``perf_counter`` clock."""
    from benchmark import training

    return [dict(s, t0=s["t0"] - wall_minus_perf, t1=s["t1"] - wall_minus_perf)
            for s in training.read_spans(obs_dir)]


def reduce_run_trace(run: Run, result: dict, spans: list[dict]):
    """The traced stretch, reduced in-process; the raw trace is deleted."""
    from benchmark import trace_reduce

    files = glob.glob(os.path.join(run.trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    st = run.stretch
    if st is None or st.error or not files:
        log(f"no trace to reduce ({st.error if st else 'no step listener'})")
        return None
    t_reduce = time.perf_counter()
    trace = trace_reduce.load_xplane(files[-1])
    offset = trace_reduce.sync_offset_ns(trace, st.sync_mark_perf)
    host_spans, background, window = [], [], None
    if offset is not None:
        on_clock = lambda t: t * 1e9 - offset  # noqa: E731
        names = set(result.get("step_spans", ()))
        waits = set(result.get("background_spans", ()))
        host_spans = [(s["name"], on_clock(s["t0"]), on_clock(s["t1"]))
                      for s in spans if s["name"] in names]
        background = [(s["name"], on_clock(s["t0"]), on_clock(s["t1"]))
                      for s in spans if s["name"] in waits]
        window = tuple(on_clock(t) for t in st.window)
    summary = trace_reduce.reduce_trace(trace, host_spans, window, background)
    shutil.rmtree(run.trace_dir, ignore_errors=True)
    busy = sum(d["busy_s"] for d in summary["devices"]) if summary else 0.0
    log(f"the trace's reduction took {time.perf_counter() - t_reduce:.2f}s; "
        f"device-busy seconds in the stretch, all chips: {busy:.3f}")
    return summary


def per_layer_metrics(manifest: dict, cell: str, reading: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something. The
    driver wants every metric the manifest gives the cell on the line, so one
    that is left out is said loudly: a metric that cannot exist in a cell
    lists the cells it does exist in under ``workloads``."""
    out = {}
    wanted = metrics_of(manifest, "per_layer", cell)
    for m in wanted:
        try:
            value = reader_of(m["name"]).read(reading)
        except Exception as e:  # said, and the metric left out of the line
            log(f"reader {m['name']} failed: {type(e).__name__}: {e}")
            value = None
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    missing = [m["name"] for m in wanted if m["name"] not in out]
    if missing:
        log(f"PER-LAYER METRICS OF {cell} WITH NOTHING TO READ, left out of "
            f"the line (the driver refuses such a line): {missing}")
    return out


def settle(result: dict, say=log) -> dict:
    """Decide ``correct``. A job returns ``compared`` (``training.Compared``:
    every number it held to a limit so far) and may return ``verify``, the
    comparison it left for now: once the window has closed, the peak has been
    read and the program's state is freed, so that a reference beside a full
    optimizer state neither sets the peak nor counts as set-up. ``correct``
    is that no number is outside its limit; ``failed`` counts those that are."""
    compared = result["compared"]
    verify = result.pop("verify", None)
    if verify is not None:
        gc.collect()
        t0 = time.perf_counter()
        verify()
        say(f"the comparison left for after the window took "
            f"{time.perf_counter() - t0:.2f}s")
    result["correct"] = not compared.failed
    result["failed"] = len(compared.failed)
    result["compared"] = compared.rows
    return result


def say_compared(rows: dict) -> None:
    """Each number compared beside its limit: the run's last lines on stderr."""
    for name, r in rows.items():
        print(f"compared {name} = {r['value']!r} {r['rule']} {r['limit']!r}"
              f"{'' if r['ok'] else '  <-- OUTSIDE ITS LIMIT'}",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    manifest, cell, workload, config = load_cell(args.workload)

    # the compile cache: where the environment says, else a fixed directory
    # inside the checkout (a directory that moves never hits)
    os.environ.setdefault(COMPILE_CACHE_ENV, os.path.join(CACHE_DIR, "jax"))
    import jax

    log("jax imported")

    # jax's default leaves programs that compile in under a second uncached
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = jax.devices()
    except RuntimeError as e:
        print(f"no accelerator: {e}", file=sys.stderr)
        return 2
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"cell {cell['name']} needs {cell['chips']} TPU chip(s); jax "
              f"sees {len(devices)} x {devices[0].platform}: not measured",
              file=sys.stderr)
        return 2
    sys.path.insert(0, ROOT)
    try:
        importlib.import_module("cst_captioning_tpu")
    except ImportError as e:
        print(f"the program is not in this checkout: {e}", file=sys.stderr)
        return 2

    from jax import monitoring

    run = Run(cell, workload, config, args.seed, args.seconds, args.trace)
    monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: run.compiles.append(
            (time.perf_counter(), float(secs)))
        if event.endswith("backend_compile_duration") else None)
    wall_minus_perf = time.time() - time.perf_counter()
    job = importlib.import_module("benchmark.jobs." + workload["job"])
    log(f"cell {cell['name']} job {workload['job']} seed {args.seed} "
        f"seconds {args.seconds} trace {args.trace} on {len(devices)} x "
        f"{devices[0].device_kind}")
    with contextlib.redirect_stdout(sys.stderr):
        result = job.run(run)
        from benchmark.training import hbm_bytes

        # the window is closed and the job has let go of the chips: read the
        # peak, then let the reference run; in a traced run that is beside
        # the profiler's stop, which the tracer thread is still waiting for
        peak = hbm_bytes(run.chips, "peak_bytes_in_use")
        result = settle(result)
        run.join_tracer()
        device = {"platform": devices[0].platform,
                  "kind": devices[0].device_kind, "count": len(devices),
                  "memory_peak_bytes": int(peak)}
        setup_s = run.t_open - T_PROCESS_START
        compile_s = sum(s for t, s in run.compiles if t <= run.t_open)
        in_window = run.compiles_in_window()
        log(f"setup_s {setup_s:.2f} (backend compile or cache load "
            f"{compile_s:.2f}s in {len(run.compiles)} programs); compiles in "
            f"window {len(in_window)} taking {sum(in_window):.4f}s; "
            f"peak {peak / 2**30:.2f} GiB; end to end {result['end_to_end']}")
        out = {"correct": bool(result["correct"]),
               "attempted": int(result["attempted"]),
               "failed": int(result["failed"]), "metrics": {}, "device": device}
        if not run.trace:
            values = dict(result["end_to_end"], setup_s=setup_s)
            for m in metrics_of(manifest, "end_to_end", cell["name"]):
                out["metrics"][m["name"]] = {"value": float(values[m["name"]]),
                                             "unit": m["unit"]}
        else:
            spans = program_spans(run.obs_dir, wall_minus_perf)
            summary = reduce_run_trace(run, result, spans)
            out["metrics"] = per_layer_metrics(manifest, cell["name"], {
                "result": result, "trace": summary, "spans": spans,
                "window": (run.t_open, run.t_close),
                "trace_window": run.stretch and run.stretch.window,
                "compiles_in_window": len(in_window),
                "config": config, "workload": workload, "chips": run.chips,
                "device_kind": devices[0].device_kind,
                "memory_peak_bytes": int(peak),
            })
            if summary is not None:
                d0 = summary["devices"][0]
                log("device 0 modules (s, runs): " + json.dumps(
                    {k: [round(v, 4), d0["module_n"][k]]
                     for k, v in sorted(d0["module_s"].items(),
                                        key=lambda kv: -kv[1])[:12]}))
                device["busy_s"] = summary["busy_s"]
                device["window_s"] = summary["window_s"]
                out["breakdown"] = summary["breakdown"]
        if run.trace:
            log(f"the line follows, {time.perf_counter() - run.t_close:.2f}s "
                "after the window closed")
        out["compared"] = result["compared"]
        say_compared(out["compared"])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
