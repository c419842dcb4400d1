#!/usr/bin/env python3
"""The harness's own tail when the device is busy: a cell's job, traced, with
``Batcher._collate`` replaced by a replay of the batches it collated in
set-up (every ``Batcher`` collates its first epoch for real and hands those
batches out again, in order, ever after), so that the host is out of the
device's way as a fast collate would put it. The log's stamps (the stretch's
seconds and steps, ``stop_trace()``, the reduction, the last line) then say
what a traced run costs the harness under a saturated device.

A tool beside ``record_trace.py``, for a change to the traced stretch. NOT a
cell and NOT a measurement of the program: its numbers go under "harness
tail, device saturated" in ``PERF.md`` and into no column of the program's.

    python3 benchmark/tests/busy_device.py --workload <cell> --seed 1 --seconds 20
"""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def replaying(collate):
    """``Batcher._collate`` that collates a batcher's first epoch and replays
    it: the k-th call returns what call ``k mod (batches an epoch)`` made,
    inside the same ``data.collate`` span, so that every reader finds its
    span and the rows, video ids and padding of a batch stay one batch's."""
    from cst_captioning_tpu import obs

    def _collate(self, items, valid):
        mine = self.__dict__.get("_bench_replay")
        if mine is None:    # num_batches() walks every record: once a batcher
            mine = self._bench_replay = {"epoch": self.num_batches(),
                                         "kept": [], "calls": 0}
        k, mine["calls"] = mine["calls"], mine["calls"] + 1
        if k < mine["epoch"]:
            mine["kept"].append(collate(self, items, valid))
            return mine["kept"][k]
        with obs.span("data.collate", rows=len(items)):
            return mine["kept"][k % mine["epoch"]]

    return _collate


def main() -> int:
    from benchmark import training

    open_trainer = training.open_trainer

    def open_trainer_replaying(ctx):
        # here, not at import: run.main has set the compile cache's place
        # and looked for the chip before anything of the program is imported
        from cst_captioning_tpu.data.batcher import Batcher

        Batcher._collate = replaying(Batcher._collate)
        ctx.log("busy_device: the collate is a replay; what follows "
                "measures the harness, NOT the program")
        return open_trainer(ctx)

    training.open_trainer = open_trainer_replaying
    return run.main(sys.argv[1:] + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
