"""Main-thread milliseconds per step inside the program's ``eval.launch``
spans: the call of the compiled decode in ``Evaluator._dispatch`` with the
fold of its key and the start of the tokens' copy to the host, an enqueue.
A launch that uploads or compiles anything shows here first."""

from benchmark.layer_metrics import _spans


def read(reading):
    return _spans.ms_per_step(reading, "eval.launch",
                              _spans.main_threads(reading))
