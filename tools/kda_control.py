#!/usr/bin/env python3
"""Run a benchmark cell with ONE of the Kimi Linear judge's controls applied.

    python tools/kda_control.py <control> --workload kimilinear_l8.diagnose_backlog --seed 7 ...

``<control>`` is a key of ``tests/test_kda_mla_moe.py::CONTROLS`` (applied to
the served path before the engine is built: ``bf16_state``, ``no_dt_bias``,
``head_decay`` — one decay a head instead of a key channel —, ``rotated_kr``,
``state_survives``, ...) or ``bf16_reference`` (the REFERENCE computed in
bfloat16, the nearest precision below the float32 the configuration states).
The rest of the line is ``benchmark/run.py``'s. The cell's line must read
``correct: false``: the builder records each control's reading in PERF.md
section 6.
"""

from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _bf16_reference(run):
    """The reference computed in the nearest precision below the one the
    configuration states: every weight, activation, sum and state bfloat16
    (products at the default precision); what the rules read comes back
    float32."""
    load = run.load_module

    def patched(kind, name):
        mod = load(kind, name)
        if kind == "references" and name == "kda_mla_moe":
            import contextlib

            import jax
            import jax.numpy as jnp

            import benchmark.references.window_gqa_moe as shared

            # (the norm and the SwiGLU are that module's, with its own cast)
            mod._f32 = shared._f32 = lambda a: a.astype(jnp.bfloat16)
            logits = mod.decoder_logits

            def coarse(*a, **kw):
                out, near, *kept = logits(*a, **kw)
                f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
                return f32(out), (f32(near[0]), near[1]), *map(f32, kept)

            mod.decoder_logits = coarse
            jax.default_matmul_precision = lambda _: contextlib.nullcontext()
        return mod

    run.load_module = patched


def main() -> int:
    control, argv = sys.argv[1], sys.argv[2:]
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    if "--rehearse" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if control == "bf16_reference":
        _bf16_reference(run)
    else:
        sys.path.insert(0, os.path.join(ROOT, "tests"))
        import test_kda_mla_moe as tests

        tests.CONTROLS[control](types.SimpleNamespace(setattr=setattr))
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
