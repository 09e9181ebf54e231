"""coalesce_wait_p50_ms — how long the oldest row of an emission waited in the
memory buffer.

Median over the emissions cut inside the window of the program's
``coalesce_wait`` span (a merged emission) or ``buffer_wait`` span (a
pass-through one): ``runtime/stream.py::_trace_emission``, from the
buffer's own monotonic clock.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "coalesce_wait", "buffer_wait")
