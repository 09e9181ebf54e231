"""peak_hbm_gb — peak device memory on the fullest chip.

``device.memory_stats()``: ``peak_bytes_in_use`` (buffers: weights, KV pool,
inputs) plus ``peak_bytes_reserved`` (the region the runtime reserves for
the compiled programs' temporaries), at the window's close, before the
reference runs, in GB of 1e9 bytes. The runtime's own counters.
"""

def read(view):
    return view.memory_peak_bytes / 1e9 if view.memory_peak_bytes else None
