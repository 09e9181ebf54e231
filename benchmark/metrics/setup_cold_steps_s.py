"""setup_cold_steps_s — wall time with a program's first call in flight.

The counter ``arkflow_setup_cold_seconds_total`` at the window's open: wall
seconds during which at least one served program was in its FIRST call
(trace, lower, compile or cache load, first execution;
``obs/startup.py::cold_step``, entered where ``serving.py::_note_step`` /
``runner.py::_note_shape`` meet a first-seen key). Not the sum of
``arkflow_stage_seconds{stage=setup_cold_step, program}``: the classify
path's workers can each meet a first-seen shape at once, and that sum would
count the instant twice. Nothing on a program without the counter's gauge.
"""

from benchmark.lib.setup import COLD, counter_at_open


def read(view):
    return counter_at_open(view, COLD)
