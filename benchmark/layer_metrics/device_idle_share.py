"""1 - (union of the intervals in which an operation ran on the device) /
the traced stretch; on several chips the idlest one."""


def read(reading):
    tr = reading["trace"]
    return None if tr is None else 100.0 * tr["idle_share_worst"]
