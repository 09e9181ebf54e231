"""gen_late_p95_ms — how late the load generator ran.

95th percentile, over the batches delivered inside the window, of (time the
batch was handed to the stream - the tick at which it was due). Host clock,
the harness's own stamp. A starved generator (the stream did not call
``read`` in time, or the event loop was busy) shows here and not as a fast
server.
"""

from benchmark.lib.readers import pct


def read(view):
    return pct(view.samples("gen_late_ms"), 95.0)
