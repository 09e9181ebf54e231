"""moe_decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches.

``decode_hbm_pct`` for a latent-attention model with routed experts. Needed
bytes of a decode step (``lib/costs_mla_moe.decode_step_bytes``: the output
head, the dense layer, per expert layer the attention, router and shared
experts plus THE ROUTED EXPERTS THE STEP HIT — the program's counter, mean a
layer, not all 128 — and the latent rows attended over) over 819 GB/s
(``peaks.json``) and over the median device time of the ``_decode`` program
in the trace. Lanes and context as ``decode_hbm_pct`` takes them.
"""

from benchmark.lib.costs_mla_moe import (decode_context, decode_routing,
                                         decode_step_bytes, sizes_of)
from benchmark.lib.readers import module_ms


def read(view):
    ms = module_ms(view, r"jit__decode")
    routing, ctx = decode_routing(view), decode_context(view)
    if ms is None or routing is None or ctx is None:
        return None
    nbytes = decode_step_bytes(experts_hit=routing[0], kv_tokens=ctx[1],
                               **sizes_of(view))
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
