"""backend_compiles_in_window — executables built or loaded inside the window.

Observations of ``arkflow_jax_compile_seconds{phase=backend_compile}``
between the window's open and its close (``view.hist``'s count), heard by
the program's own listener where JAX calls ``compile_or_get_cached``: an
executable built OR loaded from the persistent cache, whatever was or was
not lowered. ``compiles_in_window`` (the harness's listener) counts
lowerings; this stands beside it. Expected 0. Nothing on a program without
the series.
"""

from benchmark.lib.setup import COMPILE, open_snapshot


def read(view):
    if open_snapshot(view) is None:
        return None
    return float(view.hist(COMPILE, phase="backend_compile")[1])
