"""ssm_masked_pct — share of a prefill chunk's positions that were padding.

Counters ``arkflow_gen_ssm_masked_total`` / ``arkflow_gen_ssm_tokens_total``,
``kind=chunk`` (``tpu/serving.py::_prefill_step``, known on the host from
lengths): positions a chunk's program carried past the states (their step is
zero: the state and the conv window stay as they are) over all positions
dispatched. What fixed-size chunks cost a mix of short prompts.
"""


def read(view):
    masked = view.counter("arkflow_gen_ssm_masked_total", kind="chunk")
    valid = view.counter("arkflow_gen_ssm_tokens_total", kind="chunk")
    return None if masked + valid <= 0 else 100.0 * masked / (masked + valid)
