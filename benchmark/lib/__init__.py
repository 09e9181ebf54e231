"""The benchmark's own library: the yardstick later PRs may not change.

Traffic generation, the plugins that offer load and collect results, the
plain references and the comparison that decides ``correct``, the functions
that compute needed operations and bytes, the table of peaks, and the
reduction from the profiler's trace to metrics all live here. From the
program the benchmark takes only the system under test (config mapping ->
``EngineConfig`` -> ``Engine`` -> ``build_stream``) and its spans, counters
and kernel names.
"""
