"""``peak_bytes_in_use`` of the fullest chip after the window
(``device.memory_stats()``): the process's peak, set-up included
(``hbm_window_gib`` is the window's)."""


def read(reading):
    return reading["memory_peak_bytes"] / 2**30 or None
