"""gen_token_gap_ms — time between two consecutive tokens of one request.

Mean over the window of ``arkflow_gen_token_gap_seconds``
(``tpu/serving.py::_handle_token``, one stamp a token): the decode cadence as
a caller feels it, prefill chunks of other slots included. A percentile needs
the histogram's buckets, which ``View`` does not hand out yet.
"""


def read(view):
    gap_s, gaps = view.hist("arkflow_gen_token_gap_seconds")
    return None if gaps <= 0 else gap_s / gaps * 1e3
