"""Main-thread milliseconds per step inside the program's ``eval.h2d`` spans:
the placement of a batch's features and masks in ``Evaluator._dispatch``, the
*enqueue* of the upload (host time, not the transfer's completion), which the
``Evaluator`` makes on the loop's own thread. The ``eval`` twin of
``h2d_ms_per_step``."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "eval.h2d",
                              _spans.main_threads(reading))
