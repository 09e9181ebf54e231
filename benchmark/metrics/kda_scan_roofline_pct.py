"""kda_scan_roofline_pct — share of its roofline the chunked per-channel delta rule reaches.

The least time the chip could take for the scan of one chunk — the larger of
its needed operations (``lib/costs_kda_mla_moe.chunk_scan_flops``, every
dispatched position counted; the halving levels' products and the inverse's
ten NOT counted: the same work whatever implements it; over the bf16 peak)
and its needed bytes (``chunk_scan_bytes``: the slot's state in and out, the
operands and the log-decay a key channel in, the output back, over 819 GB/s)
— over the ``kda_chunk_scan`` kernel's device time in a ``_chunk`` execution
(``kda_scan_ms_per_chunk``). The kernel multiplies in float32 at ``highest``
(six bfloat16 passes), so the share is of a roof no float32 kernel reaches.
Not clamped.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk
from benchmark.lib.costs_kda_mla_moe import (chunk_scan_bytes, chunk_scan_flops,
                                             mixer_of, roofline_seconds, sizes_of)


def read(view):
    ms = kernel_ms_per_chunk(view, r"kda_chunk_scan")
    s = sizes_of(view)
    if ms is None or s is None or not view.peaks:
        return None
    shape = dict(tokens=int(view.proc_cfg["prefill_chunk"]),
                 layers=s["linear_layers"], **mixer_of(s))
    least, _ = roofline_seconds(chunk_scan_flops(**shape),
                                chunk_scan_bytes(**shape), view.peaks)
    return 100.0 * least / (ms * 1e-3)
