"""nh_update_hbm_pct — share of the chip's HBM bandwidth the recurrent update reaches (Nemotron-H's keys).

Needed bytes of the update of one decode step (``lib/costs_nemotron_h.
update_bytes``: per lane decoding and MAMBA layer — 6 of the 13 blocks — the
float32 state read AND written, 2 x 2 MiB, and the conv window) over 819 GB/s
(``peaks.json``) and over the ``ssm_state_update`` kernel's device time in a
``_decode`` execution. Lanes decoding: ``arkflow_gen_ssm_tokens_total
{kind=decode}`` over the decode steps. (``ssm_update_hbm_pct`` counts a state
in every layer and would read 13 / 6 too high here.)
"""

from benchmark.lib.costs_hybrid_ssm import lanes_decoding
from benchmark.lib.costs_mla_moe import kernel_ms_per_decode
from benchmark.lib.costs_nemotron_h import mixer_of, sizes_of, update_bytes


def read(view):
    ms, s = kernel_ms_per_decode(view, r"ssm_state_update"), sizes_of(view)
    lanes = lanes_decoding(view)
    if ms is None or s is None or lanes is None or not view.peaks:
        return None
    nbytes = update_bytes(lanes=lanes, mamba_layers=s["mamba_layers"], **mixer_of(s))
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
