"""kda_update_hbm_pct — share of the chip's HBM bandwidth the per-channel delta rule's state update reaches.

Needed bytes of the update of one decode step (``lib/costs_kda_mla_moe.
update_bytes``: per lane decoding and linear layer the float32 state read AND
written, 2 x 32 x 65,536 B = 4 MiB, and the conv window, 73,728 B: the same
count whatever implements it) over 819 GB/s (``peaks.json``) and over the
kernel's device time in a ``_decode`` execution (``kda_update_ms_per_step``).
Lanes decoding: the program's counter
``arkflow_gen_ssm_tokens_total{kind=decode}`` over its decode steps.
"""

from benchmark.lib.costs_hybrid_ssm import lanes_decoding
from benchmark.lib.costs_kda_mla_moe import mixer_of, sizes_of, update_bytes
from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    ms = kernel_ms_per_decode(view, r"kda_state_update")
    s = sizes_of(view)
    if ms is None or s is None:
        return None
    lanes = lanes_decoding(view)
    if lanes is None:
        return None
    nbytes = update_bytes(lanes=lanes, layers=s["linear_layers"],
                          taps=s["taps"], **mixer_of(s))
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
