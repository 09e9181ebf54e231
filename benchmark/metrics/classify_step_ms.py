"""classify_step_ms — device time of one classify step.

Median duration of the executions of the compiled ``classify_step`` program
(``tpu/runner.py::_build_jitted``) on device 0 in the profiler's trace.
Device time only: the wait behind another worker's step and the fetch are
``device_dispatch_wait_p50_ms`` and ``device_fetch_ms``. A program that
predates the name has no such module and the reader returns nothing.
"""

from benchmark.lib.readers import module_ms


def read(view):
    return module_ms(view, r"jit_classify_step")
