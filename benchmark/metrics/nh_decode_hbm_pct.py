"""nh_decode_hbm_pct — share of the chip's HBM bandwidth a whole decode step reaches (Nemotron-H's keys).

Needed bytes of one lockstep decode step (``lib/costs_nemotron_h.
decode_step_bytes``: the mamba and attention blocks' weights and the head
once, the routers float32, the held experts the step HIT plus the shared one
at their published widths, each decoding lane's float32 states and conv
windows read and written on the 6 mamba layers, the K/V its queries attend on
the 2 attention layers) over 819 GB/s (``peaks.json``) and over the
``_decode`` program's device time (``decode_step_ms``'s source). The whole
step's share: what a ``perf_opt`` claim on this cell is bounded by.
"""

from benchmark.lib.costs_mla_moe import decode_context
from benchmark.lib.costs_nemotron_h import decode_step_bytes, sizes_of, step_routing
from benchmark.lib.readers import module_ms


def read(view):
    ms, ctx, s = module_ms(view, r"jit__decode"), decode_context(view), sizes_of(view)
    routing = step_routing(view, "decode")
    if ms is None or ctx is None or s is None or routing is None or not view.peaks:
        return None
    nbytes = decode_step_bytes(lanes=ctx[0], kv_tokens=ctx[1],
                               experts_hit=routing[0], **s)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
