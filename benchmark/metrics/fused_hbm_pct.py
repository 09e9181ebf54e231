"""fused_hbm_pct — share of the chip's HBM bandwidth one fused step reaches.

Bytes one chip has to read for a decode step that carries a prefill chunk —
``decode_hbm_pct``'s bytes (its share of the bf16 weights, once: the chunk's
rows go through the same pass; plus the K/V the decoding lanes attend over)
plus the K/V the chunk attends over, ``lib/costs.decode_step_bytes`` — over
819 GB/s (``peaks.json``) and over the median device time of the ``_fused``
program in the trace. Still memory bound: 144 rows do 288 FLOPs per weight
byte, but the step's time is the pass over the weights (an MLP fusion takes
what it takes in a pure decode step), so the share says how far the fused
pass sits from the weights' roof; not clamped.

The lanes' K/V term is ``decode_hbm_pct``'s with one lane fewer (the slot
whose prompt rides is busy and does not decode). The chunk's is the mean,
over the chunks of the mix's prompts longer than one chunk (a shorter one
is a one-shot prefill), of the tokens a chunk attends over: chunk i of a
prompt of n tokens reads the prompt up to its own end, min(i x C, n).
A program that does not fuse has no such module and reads nothing.
"""

import numpy as np

from benchmark.lib.costs import decode_step_bytes
from benchmark.lib.readers import module_ms


def chunk_kv_tokens(prompts, chunk: int) -> float:
    """Mean tokens one chunk of ``chunk`` attends over, the chunks of every
    prompt longer than one chunk counted once each."""
    n = np.asarray(prompts, np.int64)
    n = n[n > chunk]
    if not n.size:
        return 0.0
    k = -(-n // chunk)  # chunks of a prompt; all but the last end at i x C
    return float((chunk * k * (k - 1) // 2 + n).sum() / k.sum())


def read(view):
    ms = module_ms(view, r"jit__fused")
    busy = view.gauge("arkflow_gen_slots_busy")
    if ms is None or not busy:
        return None
    s, cfg = view.sizes, view.proc_cfg
    prompts = view.run.pool.tokens
    context = float(prompts.mean()) + cfg["max_new_tokens"] / 2
    lanes = max(sum(busy) / len(busy) - 1.0, 0.0)
    nbytes = decode_step_bytes(
        dim=s["hidden_size"], layers=s["num_hidden_layers"],
        heads=s["num_attention_heads"], kv_heads=s["num_key_value_heads"],
        ffn=s["intermediate_size"], vocab=s["vocab_size"],
        kv_tokens=lanes * context + chunk_kv_tokens(prompts, cfg["prefill_chunk"]),
        chips=view.chips)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
