"""gdn_scan_roofline_pct — share of its roofline the chunked delta rule reaches.

The least time the chip could take for the scan of one chunk — the larger of
its needed operations (``lib/costs_gdn_gqa_moe.chunk_scan_flops``, every
dispatched position counted, the inverse's ten products NOT counted, over the
bf16 peak) and its needed bytes (``chunk_scan_bytes``: the slot's state in
and out, the operands in, the output back, over 819 GB/s) — over the
``gdn_chunk_scan`` kernel's device time in a ``_chunk`` execution
(``gdn_scan_ms_per_chunk``). At the cell's sizes the bytes bind (by the
file's own count: ``roofline_seconds``); the kernel multiplies in float32 at
``highest`` (six bfloat16 passes), so the share is of a roof no float32
kernel reaches. Not clamped.
"""

from benchmark.lib.costs_gdn_gqa_moe import (chunk_scan_bytes, chunk_scan_flops,
                                             mixer_of, roofline_seconds, sizes_of)
from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk


def read(view):
    ms = kernel_ms_per_chunk(view, r"gdn_chunk_scan")
    s = sizes_of(view)
    if ms is None or s is None or not view.peaks:
        return None
    tokens = int(view.proc_cfg["prefill_chunk"])
    shape = dict(tokens=tokens, layers=s["linear_layers"], **mixer_of(s))
    least, _ = roofline_seconds(
        chunk_scan_flops(**shape),
        chunk_scan_bytes(key_heads=s["key_heads"], **shape), view.peaks)
    return 100.0 * least / (ms * 1e-3)
