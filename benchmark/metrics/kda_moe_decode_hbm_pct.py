"""kda_moe_decode_hbm_pct — share of the chip's HBM bandwidth a whole decode step reaches (Kimi Linear's keys).

Needed bytes of one lockstep decode step (``lib/costs_kda_mla_moe.
decode_step_bytes``: every weight once — the head, the mixers, the dense MLP,
the routers float32 —, the held experts the step HIT plus the shared one,
each decoding lane's float32 states and conv windows read and written on the
six linear layers, the latent rows and shared keys its queries may attend on
the two latent layers) over 819 GB/s (``peaks.json``) and over the
``_decode`` program's device time (``decode_step_ms``'s source: the module's
executions in the trace).
"""

from benchmark.lib.costs_kda_mla_moe import (decode_routing, decode_step_bytes,
                                             sizes_of)
from benchmark.lib.costs_mla_moe import decode_context
from benchmark.lib.readers import module_ms


def read(view):
    s = sizes_of(view)
    if s is None:
        return None
    ms, ctx, hit = module_ms(view, r"jit__decode"), decode_context(view), decode_routing(view)
    if ms is None or ctx is None or hit is None:
        return None
    nbytes = decode_step_bytes(experts_hit=hit, lanes=ctx[0], context=ctx[1], **s)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
