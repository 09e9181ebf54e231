"""classify_steps_per_read — device steps one read is served as.

Mean over the window of the histogram ``arkflow_tpu_steps_per_batch``
(``plugins/processor/tpu_inference.py::_infer``): the (rows, seq) pieces one
batch's rows were carved into by token length before the classify step
(``tpu/bucketing.py::carve_by_length``); 1 = not split. More steps dispatch
fewer padded slots (``pad_waste_pct``) at a fixed cost a step. A program
that predates the split has no such histogram and the reader returns
nothing.
"""


def read(view):
    steps, batches = view.hist("arkflow_tpu_steps_per_batch")
    return None if batches <= 0 else steps / batches
