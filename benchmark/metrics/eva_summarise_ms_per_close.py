"""eva_summarise_ms_per_close — device time of one layer's window close.

Seconds of the close loops (``paged_decode._eva_close``: a ``while`` over the
step's closing rows — the gather of the window's 128 pages, the
``eva_summarise`` kernel pooling its 2,048 rows into 128, the scatter of 8
summary pages) on device 0 in the profiler's trace, over the rows they
closed: ONE layer's close of ONE row. A loop is found as the smallest op that
holds an ``eva_summarise`` event (``lib/costs_eva.close_calls``). Closes
happen in a prompt's every fourth chunk and in the decode steps whose lane
writes a window's last row. Reads nothing where no window closed in the
traced stretch.
"""

from benchmark.lib.costs_eva import close_calls, sizes_of


def read(view):
    if sizes_of(view) is None:
        return None
    found = close_calls(view)
    return None if found is None else found[0] / found[1] * 1e3
