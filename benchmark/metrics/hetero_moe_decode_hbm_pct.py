"""hetero_moe_decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches: the cell's share of the whole step.

``decode_hbm_pct`` for a per-head model whose layers' sizes go by kind, with
a held share of routed experts. Needed bytes of a decode step
(``lib/costs_hetero_gqa_moe.decode_step_bytes``: every weight once — the
output head, each layer's attention at its kind's sizes, the dense layer's
SwiGLU, per expert layer the router plus THE HELD EXPERTS THE STEP HIT — and
the cache rows its lanes may attend at their published widths: their whole
context on the two full layers at 2,560 B a token, the last 128 tokens on
the five sliding layers at 5,120 B) over 819 GB/s (``peaks.json``) and over
the median device time of the ``_decode`` program in the trace. Lanes and
context as ``decode_hbm_pct`` takes them.
"""

from benchmark.lib.costs_hetero_gqa_moe import decode_step_bytes, sizes_of
from benchmark.lib.costs_mla_moe import decode_context, decode_routing
from benchmark.lib.readers import module_ms


def read(view):
    ms = module_ms(view, r"jit__decode")
    routing, ctx, sizes = decode_routing(view), decode_context(view), sizes_of(view)
    if ms is None or routing is None or ctx is None or sizes is None:
        return None
    nbytes = decode_step_bytes(experts_hit=routing[0], lanes=ctx[0],
                               context=ctx[1], **sizes)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
