"""Main-thread time per step inside ``next()`` of the batch iterator that
the program's loop consumes (the benchmark's own timer, ``LoopTimer``): the
wait on collate, prefetch and upload, the first batch of every epoch and the
``next()`` that finds the epoch at its end included."""


def read(reading):
    waits = reading["result"].get("input_waits")
    steps = reading["result"].get("steps")
    if not waits or not steps:
        return None
    t0, t1 = reading["window"]
    inside = sum(b - a for a, b in waits if a >= t0 and b <= t1)
    return 1e3 * inside / len(steps)
