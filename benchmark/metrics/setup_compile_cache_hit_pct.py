"""setup_compile_cache_hit_pct — persistent compile cache hits before the window.

``arkflow_jax_compile_cache_total{result=hit}`` over hits + misses, in
percent, at the window's open (JAX's ``/jax/compilation_cache/cache_hits`` /
``cache_misses``, heard by the program): near 100 in a warm process, near 0
in a cold one, which ``first_setup_s`` against ``setup_s`` only hints at.
Nothing where no lookup was counted or the program lacks the series.
"""

from benchmark.lib.setup import CACHE, counter_at_open


def read(view):
    hits = counter_at_open(view, CACHE, result="hit")
    misses = counter_at_open(view, CACHE, result="miss")
    if hits is None or hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)
