"""setup_unnamed_s — set-up that no stage of the program names.

(window open − process start) − (init + restore + place + build + probe +
cold steps): the interpreter and ``import jax``, the harness's pools, the
engine's and the stream's construction, the fill and the settle, and
whatever of the program's start-up is still unnamed. Process start is the
program's gauge ``arkflow_process_start_time_seconds`` (unix seconds); the
window's open is the harness's ``perf_counter`` stamp put on the unix clock.
With the five named terms it adds up to the run's ``setup_s`` plus the
interpreter's own start (the harness's clock starts at its first line).
Nothing on a program without the gauge.
"""

from benchmark.lib.setup import named_s, since_process_start_s


def read(view):
    total, named = since_process_start_s(view), named_s(view)
    return None if total is None or named is None else total - named
