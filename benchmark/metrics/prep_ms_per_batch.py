"""prep_ms_per_batch — host time to turn one emission into padded tensors.

(seconds added to ``arkflow_tpu_extract_seconds`` (Arrow -> tokens) +
seconds added to ``arkflow_tpu_infeed_prep_seconds`` (pad / stage)) over the
window, divided by the batches extracted. Host clock inside the program.
"""

def read(view):
    ext_s, ext_n = view.hist("arkflow_tpu_extract_seconds")
    prep_s, _ = view.hist("arkflow_tpu_infeed_prep_seconds")
    if ext_n <= 0:
        return None
    return (ext_s + prep_s) / ext_n * 1e3
