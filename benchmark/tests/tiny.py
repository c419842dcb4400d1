"""A tiny configuration and cells for the CPU rehearsal: the same files'
shapes as ``configs/`` and ``workloads/``, at sizes a CPU walks in seconds.
Nothing here is ever measured."""

from __future__ import annotations

import copy
import os

MODEL = {
    "vocab_size": 64, "modalities": [["resnet", 32], ["c3d", 16]],
    "encoder": "temporal_attention", "d_embed": 32, "d_hidden": 32,
    "d_att": 16, "num_layers": 1, "max_len": 12, "max_frames": 8,
    "dtype": "float32", "param_dtype": "float32",
}
OVERRIDES = {
    "model__vocab_size": 64, "model__modalities": (("resnet", 32), ("c3d", 16)),
    "model__d_embed": 32, "model__d_hidden": 32, "model__d_att": 16,
    "model__max_len": 12, "model__max_frames": 8, "model__dtype": "float32",
}
CONFIG = {
    "name": "tiny_attention", "model": MODEL, "overrides": OVERRIDES,
    "corpus": {
        "videos": 96, "refs_per_video": 5, "caption_len": [3, 6],
        "vocab_size": 64, "modalities": {"resnet": 32, "c3d": 16},
        "max_frames": 8, "min_frames": 4, "topics": 4,
        "templates_per_topic": 2, "template_noise": 0.2,
        "feature_noise": 0.05, "seed": 7,
    },
    "policy": {"preset": "msrvtt_xe_attention", "steps": 150, "batch": 32,
               "lr": 0.01, "seed": 3},
}


def meanpool_config() -> dict:
    cfg = copy.deepcopy(CONFIG)
    cfg["name"] = "tiny_meanpool"
    cfg["model"].update(modalities=[["resnet", 32]], encoder="meanpool")
    cfg["overrides"].update({"model__modalities": (("resnet", 32),)})
    cfg["corpus"]["modalities"] = {"resnet": 32}
    cfg["policy"]["preset"] = "msvd_xe_meanpool"
    return cfg


CST = {
    "job": "cst", "params": {
        "preset": "msrvtt_cst_consensus",
        "overrides": {"data__batch_size": 32, "rl__update_chunks": 5},
        "warmup_steps": 4, "epoch_keys_warmed": 200,
        "policy_check": {"sampled_len_mean": [1.0, 9.0],
                         "sampled_len_p99_max": 11},
    },
}
XE = {
    "job": "xe", "params": {
        "preset": "msvd_xe_meanpool",
        "overrides": {"data__batch_size": 64, "data__seq_per_vid": 5},
        "warmup_steps": 3,
    },
}


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
            self.cache_dir, "run", f"{workload['job']}-{chips}-{int(trace)}")
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
