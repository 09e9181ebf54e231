"""prefill_chunk_ms — device time of one prefill chunk of 128 tokens.

Median duration of the executions of the compiled ``_chunk`` program
(``tpu/serving.py::_build_jitted``) on device 0 in the profiler's trace.
"""

from benchmark.lib.readers import module_ms


def read(view):
    return module_ms(view, r"jit__chunk")
