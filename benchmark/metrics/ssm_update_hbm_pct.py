"""ssm_update_hbm_pct — share of the chip's HBM bandwidth the recurrent-state update reaches.

Needed bytes of the update of one decode step
(``lib/costs_hybrid_ssm.ssm_update_bytes``: per lane decoding and layer the
float32 state read AND written, 2 x 4 MiB at Falcon-H1-34B's sizes, and the
conv window: the same count whatever implements it) over 819 GB/s
(``peaks.json``) and over the kernel's device time in a ``_decode``
execution (``ssm_update_ms_per_step``). Lanes decoding: the program's
counter ``arkflow_gen_ssm_tokens_total{kind=decode}`` over its decode steps.
"""

from benchmark.lib.costs_hybrid_ssm import (lanes_decoding, mixer_of,
                                            ssm_update_bytes)
from benchmark.lib.costs_mla_moe import kernel_ms_per_decode


def read(view):
    ms = kernel_ms_per_decode(view, r"ssm_state_update")
    mixer = mixer_of(view)
    if ms is None or mixer is None:
        return None
    lanes = lanes_decoding(view)
    if lanes is None:
        return None
    nbytes = ssm_update_bytes(lanes=lanes, layers=view.sizes["num_hidden_layers"],
                              **mixer)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
