"""conv_moe_decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches: the cell's share of the whole step.

``decode_hbm_pct`` for a model of conv layers among narrow-head attention
layers with routed experts held whole. Needed bytes of a decode step
(``lib/costs_conv_gqa_moe.decode_step_bytes``: every weight once — the
output head, nine conv mixers and three attention mixers, two dense
SwiGLUs, per expert layer the float32 router plus THE EXPERTS THE STEP HIT —
the K and V rows its lanes may attend on the three attention layers at
2,048 B a token, and each lane's conv windows read and written) over 819
GB/s (``peaks.json``) and over the median device time of the ``_decode``
program in the trace. Lanes and context as ``decode_hbm_pct`` takes them.
"""

from benchmark.lib.costs_conv_gqa_moe import decode_step_bytes, sizes_of
from benchmark.lib.costs_mla_moe import decode_context
from benchmark.lib.readers import module_ms


def read(view):
    ms, ctx, sizes = module_ms(view, r"jit__decode"), decode_context(view), sizes_of(view)
    hit_sum, steps = view.hist("arkflow_gen_moe_experts_hit", kind="decode")
    if ms is None or ctx is None or sizes is None or steps <= 0:
        return None
    nbytes = decode_step_bytes(experts_hit=hit_sum / steps, lanes=ctx[0],
                               context=ctx[1], **sizes)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
