"""eva_decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches (a compacting window cache).

``decode_hbm_pct`` for a model whose cache compacts: needed bytes of a decode
step (``lib/costs_eva.decode_step_bytes``: every layer's matrices, head 0 of
the eight prediction heads, ``eva_phi`` / ``eva_mu``, and the cached rows its
lanes attend — the program's counter over its decode steps, NOT lanes x
context: a lane at position 30,000 holds 3,120 rows) over 819 GB/s
(``peaks.json``) and over the median device time of the ``_decode`` program
in the trace: the whole step's share.
"""

from benchmark.lib.costs_eva import decode_rows, decode_step_bytes, sizes_of
from benchmark.lib.readers import module_ms


def read(view):
    s = sizes_of(view)
    if s is None or not view.peaks:
        return None
    ms = module_ms(view, r"jit__decode")
    step = decode_rows(view)
    if ms is None or step is None:
        return None
    nbytes = decode_step_bytes(rows=step[0], lanes=step[1], **s)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
