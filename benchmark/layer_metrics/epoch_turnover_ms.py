"""Mean main-thread time between one epoch's loop being left and the next
one's being entered (``LoopTimer``): the program's epoch-end work — the wait
for the last update, the read-back of the state, the sentinel, the shuffle,
the prefetch restart, the next epoch's key. The first batch of the new epoch
is not in it: that wait is ``input_wait_ms_per_step``'s."""


def read(reading):
    t0, t1 = reading["window"]
    inside = [b - a for a, b in reading["result"].get("turnovers", ())
              if a >= t0 and b <= t1]
    return 1e3 * sum(inside) / len(inside) if inside else None
