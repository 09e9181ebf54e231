"""eva_attn_hbm_pct — share of the chip's HBM bandwidth attention reaches over a compacting window cache.

Needed bytes of the attention calls of one decode step
(``lib/costs_eva.attention_bytes``: the cached rows its lanes attend — the
program's counter ``arkflow_gen_eva_rows_attended_total{phase="decode"}``
over its decode steps: summary rows of closed windows and the open window's
exact rows, 16,384 B a row a layer — plus the queries in and out, over all
layers) over 819 GB/s (``peaks.json``) and over the kernel's device time in a
``_decode`` execution (``eva_attn_ms_per_step``).
"""

from benchmark.lib.costs_eva import attention_bytes, decode_rows, sizes_of
from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    s = sizes_of(view)
    if s is None or not view.peaks:
        return None
    ms = kernel_ms_per_decode(view, r"paged_flash_attention")
    step = decode_rows(view)
    if ms is None or step is None:
        return None
    nbytes = attention_bytes(rows=step[0], queries=step[1], layers=s["layers"],
                             heads=s["heads"], kv_heads=s["kv_heads"],
                             head_dim=s["head_dim"])
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
