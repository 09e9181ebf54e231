"""fused_step_ms — device time of one decode step that carries a prefill chunk.

Median duration of the executions of the compiled ``_fused`` program
(``tpu/serving.py::_build_jitted``; ``models/paged_decode.paged_fused_step``)
on device 0 in the profiler's trace: the step's decode lanes and one chunk
of the prompt that is prefilling, one row block through every weight
product, so one pass over the weights where ``decode_step_ms`` +
``prefill_chunk_ms`` are two. Device time only. A program that does not
fuse (the parent; a model whose chunks keep their own step) has no such
module and reads nothing.
"""

from benchmark.lib.readers import module_ms


def read(view):
    return module_ms(view, r"jit__fused")
