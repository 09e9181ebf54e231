"""conv_moe_fused_hbm_pct — share of the chip's HBM bandwidth one fused step of the conv / routed model reaches.

Needed bytes of a decode step that carries a prefill chunk —
``conv_moe_decode_hbm_pct``'s bytes (``lib/costs_conv_gqa_moe.
decode_step_bytes``: every non-expert weight ONCE, the chunk's rows go
through the same pass; per expert layer the float32 router and the experts
the BLOCK hit, lanes or chunk: the routing series ``kind="fused"``; the K
and V rows the decoding lanes may attend and their conv windows read and
written) plus the chunk's own cache bytes (the K and V rows it attends over
on the three attention layers, its slot's conv windows read and written) —
over 819 GB/s (``peaks.json``) and over the median device time of the
``_fused`` program in the trace. Needed bytes only (what is written to the
K/V pages, the activations and the embedding rows are not counted), so a
share over 100 means a count is wrong; not clamped.

The lanes are ``conv_moe_decode_hbm_pct``'s with one lane fewer (the slot
whose prompt rides is busy and does not decode). The chunk attends over
the mean, over the chunks of the mix's prompts (a model with a state pool
prefills every prompt in chunks), of min(i x C, n) tokens: chunk i of a
prompt of n tokens reads the prompt up to its own end. A program that does
not fuse (the parent) has no such module and reads nothing.
"""

import numpy as np

from benchmark.lib.costs_conv_gqa_moe import (decode_step_bytes, kv_row_bytes,
                                              sizes_of, slot_bytes)
from benchmark.lib.readers import module_ms


def chunk_kv_tokens(prompts, chunk: int) -> float:
    """Mean tokens one chunk of ``chunk`` attends over, every chunk of every
    prompt counted once: all but a prompt's last end at i x C."""
    n = np.asarray(prompts, np.int64)
    if not n.size:
        return 0.0
    k = -(-n // chunk)
    return float((chunk * k * (k - 1) // 2 + n).sum() / k.sum())


def read(view):
    ms, sizes = module_ms(view, r"jit__fused"), sizes_of(view)
    busy = view.gauge("arkflow_gen_slots_busy")
    hit_sum, steps = view.hist("arkflow_gen_moe_experts_hit", kind="fused")
    if ms is None or sizes is None or not busy or steps <= 0:
        return None
    cfg, prompts = view.proc_cfg, view.run.pool.tokens
    lanes = max(sum(busy) / len(busy) - 1.0, 0.0)
    context = float(prompts.mean()) + cfg["max_new_tokens"] / 2
    conv_layers = sizes["layers"] - sizes["attn_layers"]
    nbytes = decode_step_bytes(experts_hit=hit_sum / steps, lanes=lanes,
                               context=lanes * context, **sizes)
    nbytes += (sizes["attn_layers"] * kv_row_bytes(
        kv_heads=sizes["kv_heads"], head_dim=sizes["head_dim"])
        * chunk_kv_tokens(prompts, cfg["prefill_chunk"])
        + 2 * slot_bytes(conv_layers=conv_layers, taps=sizes["taps"],
                         hidden=sizes["hidden"]))
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
