"""pad_waste_pct — share of the token slots dispatched that held padding.

100 x (1 - real tokens / padded tokens dispatched) over the window, from the
runner's counters ``arkflow_tpu_tokens_total`` (attention-mask sum) and
``arkflow_tpu_token_capacity_total`` (bucket rows x padded seq).
"""

def read(view):
    cap = view.counter("arkflow_tpu_token_capacity_total")
    if cap <= 0:
        return None
    return 100.0 * (1.0 - view.counter("arkflow_tpu_tokens_total") / cap)
