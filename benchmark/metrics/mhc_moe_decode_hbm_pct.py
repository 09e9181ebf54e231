"""mhc_moe_decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches (several residual streams).

``moe_decode_hbm_pct`` for a latent-attention model with a query latent, a
held share of the experts and several residual streams. Needed bytes of a
decode step (``lib/costs_mhc_mla_moe.decode_step_bytes``: the output head;
per layer the attention's matrices — ``q_a`` / ``q_b``, not one ``q_proj``
— and both sub-layers' mixing; the dense SwiGLUs; per expert layer the
float32 router over ALL its outputs, the shared expert and THE HELD EXPERTS
THE STEP HIT — the program's counter, mean a layer —; and the latent rows
and rope keys attended over at their PUBLISHED widths, 576 values a token a
layer, though the pool holds 640) over 819 GB/s (``peaks.json``) and over the
median device time of the ``_decode`` program in the trace: the whole step's
share. Lanes and context as ``decode_hbm_pct`` takes them: mean busy slots x
(mean prompt + half of ``max_new_tokens``).
"""

from benchmark.lib.costs_mhc_mla_moe import decode_step_bytes, sizes_of
from benchmark.lib.costs_mla_moe import decode_context, decode_routing
from benchmark.lib.readers import module_ms


def read(view):
    s = sizes_of(view)
    if s is None or not view.peaks:
        return None
    ms = module_ms(view, r"jit__decode")
    routing, ctx = decode_routing(view), decode_context(view)
    if ms is None or routing is None or ctx is None:
        return None
    nbytes = decode_step_bytes(experts_hit=routing[0], kv_tokens=ctx[1],
                               lanes=ctx[0], **s)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
