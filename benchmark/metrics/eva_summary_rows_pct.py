"""eva_summary_rows_pct — summary rows' share of the cache rows decode steps attended.

The program's counter ``arkflow_gen_eva_rows_attended_total{phase="decode"}``
over the window: rows of kind ``summary`` (128 a closed window) over all rows
attended. Says whether the traffic reached the mechanism: 0 where no lane's
context passed a window's end, ~48 % at a context of 32k.
"""


def read(view):
    name = "arkflow_gen_eva_rows_attended_total"
    summary = view.counter(name, phase="decode", kind="summary")
    total = view.counter(name, phase="decode")
    return None if total <= 0 else 100.0 * summary / total
