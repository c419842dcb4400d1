#!/usr/bin/env python3
"""How ``recorded_trace.json`` and ``recorded_trace_dp4.json`` were made: a
traced run of a cell on the chip, with what the reduction was given (the
planes, lines and events ``load_xplane`` extracted, the host spans, the
window) written out before the raw trace is deleted. The committed files are
cuts of such a dump, with the numbers read off the events by hand.

    python3 benchmark/tests/record_trace.py <out.json> --workload <cell> \
        --seed 1 --seconds 20 --trace 1
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark import run, trace_reduce  # noqa: E402


def main() -> int:
    out, argv = sys.argv[1], sys.argv[2:]
    reduce_trace = trace_reduce.reduce_trace

    def dumping(trace, host_spans=(), window=None, background_spans=()):
        with open(out, "w") as f:
            json.dump({"trace": trace, "host_spans": host_spans,
                       "background_spans": background_spans,
                       "window": window}, f)
        return reduce_trace(trace, host_spans, window, background_spans)

    trace_reduce.reduce_trace = dumping
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
