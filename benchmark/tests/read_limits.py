#!/usr/bin/env python3
"""The readings a cell's limits are set from, on the chip, in one process:

    python benchmark/tests/read_limits.py --workload <cell> --seeds 11,12,13 \\
        [--seconds 2] [--control float8_e4m3fn] [--out chiprun_out/limits.jsonl]

For each seed the cell's job runs as ``run.py`` runs it, with a short window
(set-up, the first epoch with the followed steps, at the cell's own batch,
rollouts, chunks and chips), and ``run.settle`` decides ``correct``: the sound
readings. Then the control: the configuration's reference, computed with every
matrix product's operands rounded to ``--control`` (the nearest precision
below the one the configuration states), is put in the program's place on the
same steps and held to the float32 reference by the same comparison, and the
64-clip log-probability check is read the same way. One JSON line a seed:
``{"seed", "sound": {name: value}, "control": {name: value}, "correct"}``.
The benchmark's own runs never run this; a tool, like ``record_trace.py``.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control", default="float8_e4m3fn")
    ap.add_argument("--out", default="chiprun_out/limits.jsonl")
    args = ap.parse_args(argv)

    from benchmark import run as bench_run
    _manifest, cell, workload, config = bench_run.load_cell(args.workload)
    os.environ.setdefault(bench_run.COMPILE_CACHE_ENV,
                          os.path.join(bench_run.CACHE_DIR, "jax"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print("needs the cell's TPU chips: not measured", file=sys.stderr)
        return 2
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    job = importlib.import_module("benchmark.jobs." + workload["job"])
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.Run(cell, workload, config, seed, args.seconds, 0)
        result = job.run(run)
        first = result["followed"]
        result = bench_run.settle(result)
        bench_run.say_compared(result["compared"])
        sound = {k: r["value"] for k, r in result["compared"].items()}
        held = first.control(args.control, bench_run.log)
        control = {k: r["value"] for k, r in held.rows.items()}
        line = {"seed": seed, "cell": cell["name"], "control_precision": args.control,
                "correct": result["correct"], "sound": sound, "control": control}
        bench_run.say_compared(held.rows)
        print("LIMITS " + json.dumps(line), flush=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(line) + "\n")
        del first, result, held, run
    return 0


if __name__ == "__main__":
    sys.exit(main())
