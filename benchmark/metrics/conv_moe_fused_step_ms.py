"""conv_moe_fused_step_ms — device time of one decode step of the conv / narrow-head / routed model that carries a prefill chunk.

Median duration of the executions of the compiled ``_fused`` program
(``tpu/serving.py::_build_jitted``; ``models/paged_decode.paged_fused_step``
over ``_dense_layers``) on device 0 in the profiler's trace: 128 lanes and
one 256-token chunk of the prompt that is prefilling, ONE block of 384 rows
through every norm, projection, router and expert product, a conv layer's
windows read and written a part at a time (``_conv_fused``) — one pass over
the weights where ``decode_step_ms`` + ``prefill_chunk_ms`` were two. Device
time only. A program that does not fuse (the parent) has no such module and
reads nothing.
"""

from benchmark.lib.readers import module_ms


def read(view):
    return module_ms(view, r"jit__fused")
