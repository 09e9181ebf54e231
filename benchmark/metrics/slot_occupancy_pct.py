"""slot_occupancy_pct — share of the decode slots holding a request.

Mean over the window of the gauge ``arkflow_gen_slots_busy`` (admitting +
decoding slots), sampled every 50 ms by the harness, over the configured
``slots``.
"""

def read(view):
    s = view.gauge("arkflow_gen_slots_busy")
    slots = int(view.proc_cfg.get("slots", 0))
    if not s or not slots:
        return None
    return 100.0 * sum(s) / len(s) / slots
