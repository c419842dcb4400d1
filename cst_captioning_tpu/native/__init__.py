"""Native host kernels (C++ via ctypes; auto-built, pure-Python fallback).

The reference has no first-party native code (its jars are JVM metric tools,
SURVEY.md §2 "native components"); this framework's native layer accelerates
the RL reward host path, per the SURVEY's design note: "implement a small C++
extension … with a pure-numpy fallback".
"""

from cst_captioning_tpu.native.build import load_creward, load_error

__all__ = ["load_creward", "load_error"]
