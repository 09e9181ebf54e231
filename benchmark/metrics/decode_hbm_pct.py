"""decode_hbm_pct — share of the chip's HBM bandwidth one decode step reaches.

Bytes one chip has to read for a decode step — its share of the bf16
weights plus the K/V of every token the active slots attend over,
``lib/costs.decode_step_bytes`` — over 819 GB/s (``peaks.json``) and over the
median device time of the ``_decode`` program in the trace. Memory bound: 16
lanes do 32 FLOPs per weight byte against a ridge of 240. The K/V term uses
the mean number of busy slots (gauge samples) times the mean context, taken
as the mix's mean prompt plus half of ``max_new_tokens``. Needed bytes, not
the program's: today it reads float32 masters and casts them every step.
"""

from benchmark.lib.costs import decode_step_bytes
from benchmark.lib.readers import module_ms


def read(view):
    ms = module_ms(view, r"jit__decode")
    busy = view.gauge("arkflow_gen_slots_busy")
    if ms is None or not busy:
        return None
    s = view.sizes
    context = float(view.run.pool.tokens.mean()) + view.proc_cfg["max_new_tokens"] / 2
    nbytes = decode_step_bytes(
        dim=s["hidden_size"], layers=s["num_hidden_layers"],
        heads=s["num_attention_heads"], kv_heads=s["num_key_value_heads"],
        ffn=s["intermediate_size"], vocab=s["vocab_size"],
        kv_tokens=sum(busy) / len(busy) * context, chips=view.chips)
    return 100.0 * nbytes / view.peaks["hbm_bytes_per_s"] / (ms * 1e-3)
