"""bert_step_mxu_pct — share of the chip's bf16 peak one full classify step reaches.

Needed FLOPs of one step at the (batch, seq) bucket dispatched most during
the trace — real and padding tokens alike, ``lib/costs.bert_forward_flops``
— over the peak (``peaks.json``) and over the median device time of the
program that ran most in the trace. Read only when at least 90 % of the
steps dispatched during the trace were of that one bucket (else the program
that ran most cannot be tied to a shape and the reader returns nothing).
Compute bound: at 1,024 x 512 the step is 99 TFLOP against 0.2 GB of weights.
"""

from benchmark.lib.costs import bert_forward_flops
from benchmark.lib.stats import median


def read(view):
    t = view.trace
    if not t or not t.get("modules") or not view.shapes_in_trace:
        return None
    (batch, seq), steps = max(view.shapes_in_trace.items(), key=lambda kv: kv[1])
    if steps < 0.9 * sum(view.shapes_in_trace.values()):
        return None
    durs = max(t["modules"].values(), key=sum)
    s = view.sizes
    flops = bert_forward_flops(
        hidden=s["hidden_size"], layers=s["num_hidden_layers"],
        ffn=s["intermediate_size"], batch=batch, seq=seq)
    return 100.0 * flops / view.peaks["bf16_flops_per_s"] / median(durs)
