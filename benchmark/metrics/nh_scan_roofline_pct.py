"""nh_scan_roofline_pct — share of its roofline the chunked scan reaches (Nemotron-H's keys).

The least time the chip could take for the scan of one chunk over the 6 mamba
layers — the larger of its needed operations (``lib/costs_nemotron_h.
scan_counts``, every dispatched position counted, over the bf16 peak) and its
needed bytes (the row's state in and out, the operands in, the output back,
over 819 GB/s) — over the ``ssm_chunk_scan`` kernel's device time in a
``_chunk`` execution. The kernel multiplies in float32 at ``highest`` (six
bfloat16 passes): the share is of a roof no float32 kernel reaches. Not
clamped.
"""

from benchmark.lib.costs_hybrid_ssm import kernel_ms_per_chunk
from benchmark.lib.costs_nemotron_h import roofline_seconds, scan_counts, sizes_of


def read(view):
    ms, s = kernel_ms_per_chunk(view, r"ssm_chunk_scan"), sizes_of(view)
    if ms is None or s is None or not view.peaks:
        return None
    flops, nbytes = scan_counts(
        tokens=int(view.proc_cfg["prefill_chunk"]), block=view.sizes["chunk_size"],
        mamba_layers=s["mamba_layers"], d_ssm=s["d_ssm"], groups=s["groups"],
        d_state=s["d_state"], mixer_heads=s["mixer_heads"])
    return 100.0 * roofline_seconds(flops, nbytes, view.peaks)[0] / (ms * 1e-3)
