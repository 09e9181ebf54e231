"""ssm_scan_roofline_pct — share of its roofline the chunked state scan reaches.

The least time the chip could take for the scan of one chunk — the larger of
its needed operations (``lib/costs_hybrid_ssm.chunk_scan_flops``, every
dispatched position counted, over the bf16 peak) and its needed bytes
(``chunk_scan_bytes``: the slot's state in and out, the operands in, the
output back, over 819 GB/s) — over the ``ssm_chunk_scan`` kernel's device
time in a ``_chunk`` execution (``ssm_scan_ms_per_chunk``). At the cell's
sizes the bytes bind (by the file's own count: ``roofline_seconds``).
"""

from benchmark.lib.costs_hybrid_ssm import (chunk_scan_bytes, chunk_scan_flops,
                                            kernel_ms_per_chunk, mixer_of,
                                            roofline_seconds)


def read(view):
    ms = kernel_ms_per_chunk(view, r"ssm_chunk_scan")
    mixer = mixer_of(view)
    if ms is None or mixer is None or not view.peaks:
        return None
    s, tokens = view.sizes, int(view.proc_cfg["prefill_chunk"])
    shape = dict(layers=s["num_hidden_layers"], d_ssm=mixer["d_ssm"],
                 groups=mixer["groups"], d_state=mixer["d_state"])
    least, _ = roofline_seconds(
        chunk_scan_flops(tokens=tokens, block=s["mamba_chunk_size"], **shape),
        chunk_scan_bytes(tokens=tokens, mixer_heads=s["mamba_n_heads"], **shape),
        view.peaks)
    return 100.0 * least / (ms * 1e-3)
