"""Device memory the window holds, fullest chip: ``peak_bytes_in_use`` if it
rose inside the window; else the peak was set in set-up and cannot be reset,
and this is the largest ``bytes_in_use`` sampled at the step completions in
the window — the live buffers between programs, a lower bound on the
window's own peak (a program's temporaries are not in it)."""


def read(reading):
    hbm = reading["result"].get("hbm")
    if not hbm:
        return None
    rose = hbm["peak_at_close"] > hbm["peak_at_open"]
    return (hbm["peak_at_close"] if rose else hbm["live_max"]) / 2**30 or None
