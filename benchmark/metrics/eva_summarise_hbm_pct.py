"""eva_summarise_hbm_pct — share of the chip's HBM bandwidth a window close reaches.

Needed bytes of one close of one layer (``lib/costs_eva.summarise_bytes``:
the window's 2,048 rows read ONCE, 33.5 MB, its 128 summary rows written,
2.1 MB) over 819 GB/s (``peaks.json``) and over the close's device time
(``eva_summarise_ms_per_close``: gather + kernel + scatter; the program
reads the window out of the pools into a temporary and the kernel reads that,
so it moves the rows twice where a kernel that walked the table itself would
move them once).
"""

from benchmark.lib.costs_eva import close_calls, sizes_of, summarise_bytes


def read(view):
    s = sizes_of(view)
    if s is None or not view.peaks:
        return None
    found = close_calls(view)
    if found is None:
        return None
    read_b, written = summarise_bytes(
        window=view.sizes["window_size"], chunk=view.sizes["chunk_size"],
        kv_heads=s["kv_heads"], head_dim=s["head_dim"])
    return (100.0 * (read_b + written) * found[1]
            / view.peaks["hbm_bytes_per_s"] / found[0])
