"""eva_window_pages_share_of_cache_pct — the open windows' share of the pages slots hold.

The harness samples the gauges ``arkflow_gen_eva_live_pages_window`` and
``arkflow_gen_eva_live_pages_summary`` (``tpu/serving.py::_eva_gauges``):
mean window pages over mean pages held. A cache that kept a row a position
would hold 8.2 pages for every one of these at the mix's mean context.
"""


def read(view):
    window = view.gauge("arkflow_gen_eva_live_pages_window")
    summary = view.gauge("arkflow_gen_eva_live_pages_summary")
    if not window or not summary:
        return None
    w, s = sum(window) / len(window), sum(summary) / len(summary)
    return None if w + s <= 0 else 100.0 * w / (w + s)
