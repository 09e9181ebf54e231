"""moe_held_assignments_pct — share of the routed (token, expert) pairs that land on the experts held here.

Pairs routed to the experts this chip holds over all pairs routed (counters
``arkflow_gen_moe_held_assignments_total`` / ``arkflow_gen_moe_assignments_
total``, counted on the device inside each step, ``decoder.py::
moe_step_stats``), decode steps and prefill chunks together. A chip that
holds 32 of 256 experts under a balanced router sees 12.5 %; more means this
share is hot and the step reads and multiplies more than its eighth.
"""


def read(view):
    held = view.counter("arkflow_gen_moe_held_assignments_total")
    pairs = view.counter("arkflow_gen_moe_assignments_total")
    return None if pairs <= 0 or held <= 0 else 100.0 * held / pairs
