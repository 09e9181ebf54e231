"""setup_compile_s — what JAX spent compiling before the window opened.

``arkflow_jax_compile_seconds`` summed over ``phase`` = ``trace``, ``lower``,
``backend_compile`` and over programs, at the window's open (the program's
own ``jax.monitoring`` listeners). It lies INSIDE the stages — the served
programs' in the cold steps, ``other`` in init, placement and the probe —
and is reported beside them, never added to them. ``cache_retrieval`` lies
inside ``backend_compile`` (JAX records that one on a cache hit too) and is
left out of the sum. Nothing on a program without the series.
"""

from benchmark.lib.setup import compile_s


def read(view):
    return compile_s(view)
