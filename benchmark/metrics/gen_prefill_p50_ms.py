"""gen_prefill_p50_ms — slot to first token.

Median over the window of the program's ``gen_prefill`` span
(``tpu/serving.py::_stamp_ttft``): from the request getting its slot to its
first token, so every prefill chunk and every decode step of the other slots
that ran in between. With ``gen_queue_wait`` it is the time to first token.
"""

from benchmark.lib.readers import span_ms


def read(view):
    return span_ms(view, "gen_prefill")
