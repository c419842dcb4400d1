"""A stand-in for what the harness hands a reader of the cell
``zaya1_8b_20l.eval_beam5_p16k`` after a traced run: the reduction's summary
of a made-up stretch of two steps on one device (two executions of
``jit_eval_prefill`` and of the compiled beam search; the latent attention's
kernel is ONE operation there, inside the loop over the layers, so its 19 runs
a step sum under one name; other operations beside it), the job's steps on
the benchmark's clock, and the cell's own configuration. The readers PR 50
brought are fed from here, each in a test file of its own; nothing here is a
measurement."""

from __future__ import annotations

import copy

from benchmark.tests import tiny

CELL = "zaya1_8b_20l.eval_beam5_p16k"
KERNEL_S = 2 * 19 * 0.012       # two steps x 19 layers' attention
OPS = {
    "cca_attn_prefill.1": KERNEL_S,
    "cca_attn_prefill_fusion.3": 5.0,           # other operations' names
    "cca_attn_prefill.1.remat": 7.0,
    "full_attn_prefill.2": 3.0,                 # another kind's kernel
    "fusion.12": 1.9, "convolution_add_fusion.4": 0.6, "while.3": 0.001,
}
MODULES = {"jit_eval_prefill": 1.10, "jit__lambda": 1.30, "jit_other": 9.0}


def reading(ops=OPS, modules=MODULES, steps=2):
    config = tiny.config_file("zaya1_8b_20l")
    window = (100.0, 104.0)
    return {
        "config": copy.deepcopy(config),
        "workload": tiny.workload_file(CELL),
        "chips": 1, "device_kind": "TPU v5 lite",
        "window": (90.0, 120.0), "trace_window": window,
        "spans": [],
        "trace": {"window_s": 4.0, "busy_s": 3.4, "devices": [{
            "device": 0, "busy_s": 3.4, "op_self_s": dict(ops),
            "module_s": dict(modules),
            "module_n": {k: steps for k in modules},
            "module_runs_s": {k: [v / steps] * steps for k, v in modules.items()},
        }]},
        "result": {
            # completions: one before the stretch, ``steps`` inside, one after
            "steps": [(99.0, 2)] + [(100.5 + 3.0 * i / steps, 2) for i in range(steps)]
            + [(110.0, 2)],
            "modules": {"eval_decode": r"^jit_\w*_lambda_?$"},
            "cost_shape": {"kind": "eval", "B": 2, "beam": 5, "profile": None},
        },
    }
