#!/usr/bin/env python3
"""Run a benchmark cell with ONE of the Nemotron-H judge's controls applied.

    python tools/nemotron_control.py <control> --workload nemotron3_l13.agent_backlog --seed 7 ...

``<control>``: ``bf16_reference`` — the REFERENCE computed in bfloat16, the
nearest precision below the one the configuration states (weights, sums and
the recurrent state bfloat16; products at the default precision) —,
``bf16_state`` — the SERVED state pool held in bfloat16 —, ``no_skip`` — the
served mixer without ``D x_t``. The rest of the line is ``benchmark/run.py``'s.
The cell's line must read ``correct: false``: the builder records each
control's reading in PERF.md section 6.
"""

from __future__ import annotations

import importlib.util
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _bf16_reference(run):
    load = run.load_module

    def patched(kind, name):
        mod = load(kind, name)
        if kind == "references" and name == "nemotron_h":
            import contextlib

            import jax
            import jax.numpy as jnp

            mod._f32 = lambda a: a.astype(jnp.bfloat16)
            mod._F32_LEAVES = ()
            jax.default_matmul_precision = lambda _: contextlib.nullcontext()
        return mod

    run.load_module = patched


def _bf16_state():
    import jax.numpy as jnp

    from arkflow_tpu.models import paged_decode as pd
    from arkflow_tpu.tpu import serving

    init = pd.init_page_pool

    def coarse(*a, **kw):
        kp, vp = init(*a, **kw)
        return {**kp, "ssm": kp["ssm"].astype(jnp.bfloat16)}, vp

    pd.init_page_pool = serving.init_page_pool = coarse


def _no_skip():
    import jax.numpy as jnp

    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.models import paged_decode as pd

    output = dec.ssm_output

    def skipless(lp, o, x, z, cfg, dtype):
        return output({**lp, "ssm_D": jnp.zeros_like(lp["ssm_D"])}, o, x, z, cfg,
                      dtype)

    dec.ssm_output = pd.ssm_output = skipless


def main() -> int:
    control, argv = sys.argv[1], sys.argv[2:]
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(ROOT, "benchmark", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    if "--rehearse" in argv:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if control == "bf16_reference":
        _bf16_reference(run)
    else:
        {"bf16_state": _bf16_state, "no_skip": _no_skip}[control]()
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
