"""Programs compiled (or fetched from the compile cache) inside the window,
counted by the benchmark's ``backend_compile_duration`` listener. Must be 0:
anything else is compilation paid inside the measured window."""


def read(reading):
    return reading["compiles_in_window"]
