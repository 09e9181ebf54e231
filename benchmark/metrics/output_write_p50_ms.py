"""output_write_p50_ms — time to write one processed emission to the sink.

Median over the window of the program's ``output_write`` span
(``runtime/stream.py::_emit``). The sink is the benchmark's own and only
stamps and keeps the batch, so this is the stream's write path, not a
broker.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "output_write")
