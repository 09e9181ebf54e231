"""hybrid_decode_hbm_pct — share of the chip's HBM bandwidth one decode step of a hybrid model reaches.

``decode_hbm_pct`` for a model with a mixer beside its attention. Needed
bytes of a decode step (``lib/costs_hybrid_ssm.decode_step_bytes``: every
layer's weights and the output head once, the recurrent state of the lanes
decoding read and written, the K/V of their contexts) over 819 GB/s
(``peaks.json``) and over the median device time of the ``_decode`` program
in the trace. Lanes: the program's counter over its decode steps; context:
the mix's mean prompt plus half of ``max_new_tokens`` (the rule of
``decode_hbm_pct``).
"""

from benchmark.lib.costs_hybrid_ssm import (decode_step_bytes, lanes_decoding,
                                            layer_sizes_of, mixer_of)
from benchmark.lib.readers import module_ms


def read(view):
    ms = module_ms(view, r"jit__decode")
    mixer = mixer_of(view)
    if ms is None or mixer is None:
        return None
    lanes = lanes_decoding(view)
    if lanes is None:
        return None
    context = float(view.run.pool.tokens.mean()) + view.proc_cfg["max_new_tokens"] / 2
    nbytes = decode_step_bytes(lanes=lanes, kv_tokens=lanes * context,
                               **layer_sizes_of(view), **mixer)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
