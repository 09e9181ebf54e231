#!/usr/bin/env python3
"""A traced run of a benchmark cell with the UNLISTED ``nh_*`` readers applied.

    python tools/nemotron_readers.py --workload nemotron3_l13.agent_backlog --seed 7 ...

``BENCHMARK.json`` holds 128 per-layer metrics, the contract's limit, so the
eight readers of the one-mixer blocks (``benchmark/metrics/nh_*.py``) ride as
files until a ``benchmark`` PR makes room. This runs ``benchmark/run.py`` as
it stands (it is not edited), with ``--trace 1``, and adds the eight to the
metrics the cell's traced line reads, under their file names and units: the
builder states each kernel's share of its roofline in PERF.md from that line.
The rest of the command line is ``benchmark/run.py``'s.
"""

from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

UNLISTED = {"nh_update_hbm_pct": "%", "nh_scan_roofline_pct": "%",
            "nh_expert_ms_per_step": "ms", "nh_expert_hbm_pct": "%",
            "nh_experts_hit_pct": "%", "nh_grouped_chunks_pct": "%",
            "nh_decode_hbm_pct": "%", "nh_state_share_of_cache_pct": "%"}


def main() -> int:
    argv = [a for a in sys.argv[1:]]
    if "--trace" in argv:
        at = argv.index("--trace")
        del argv[at:at + 2]
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    if "--rehearse" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    listed = run.metrics_for

    def with_unlisted(bench, section, workload):
        found = listed(bench, section, workload)
        if section != "per_layer":
            return found
        return found + [{"name": name, "unit": unit}
                        for name, unit in UNLISTED.items()]

    run.metrics_for = with_unlisted
    return run.main([*argv, "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
