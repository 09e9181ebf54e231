"""input_lag_p95_ms — due -> read by the stream.

95th percentile over the window's rows of (time the stream's ``read``
returned the row - time the row was due). Includes the wait for the 5 ms
tick, the generator's lateness and any back-pressure that kept the stream
from reading. Host clock, the harness's own stamps.
"""

from benchmark.lib.readers import pct


def read(view):
    return pct(view.samples("input_lag_ms"), 95.0)
