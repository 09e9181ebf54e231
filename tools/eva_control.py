#!/usr/bin/env python3
"""Run a benchmark cell with ONE of the EVA judge's controls applied.

    python tools/eva_control.py <control> --workload evabyte_l8.rawlog_backlog --seed 7 ...

``<control>`` is a key of ``tests/test_eva_decoder.py::CONTROLS`` (applied to
the served path before the engine is built) or ``sliding_reference`` (the
REFERENCE's window slides instead of blocking: the served path has no sliding
form to switch to, and the distance the rules see is the same one) or
``bf16_reference`` (the reference computed in bfloat16, the nearest precision
below the float32 the configuration states). The rest
of the line is ``benchmark/run.py``'s. The cell's line must read ``correct:
false``: the builder records each control's reading in PERF.md section 6.
"""

from __future__ import annotations

import os
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def _sliding_reference(run):
    """``eva_attention`` of the reference with a window that slides: a query
    sees its last ``window`` keys exactly and every chunk wholly behind them
    through its summary."""
    load = run.load_module

    def patched(kind, name):
        mod = load(kind, name)
        if kind == "references" and name == "eva_dense_decoder":
            mod.eva_attention = _sliding_attention(mod)
        return mod

    run.load_module = patched


def _sliding_attention(ref):
    def eva_attention(q, k, v, phi, mu, hp, block=512):
        import jax
        import jax.numpy as jnp

        n, heads, d = q.shape
        w, c = hp["window"], hp["chunk"]
        padded = -(-n // w) * w
        pad = ((0, padded - n), (0, 0), (0, 0))
        q, k, v = (jnp.pad(a, pad) for a in (q, k, v))
        group = heads // k.shape[1]
        ks, vs = ref.summaries(k, v, phi, mu, c)
        last = jnp.arange(padded // c) * c + c - 1
        rep = lambda a: jnp.repeat(a, group, axis=1)  # noqa: E731
        kx = jnp.pad(k, ((w, 0), (0, 0), (0, 0)))      # keys start - w ..
        vx = jnp.pad(v, ((w, 0), (0, 0), (0, 0)))

        def one(start):
            qb = jax.lax.dynamic_slice_in_dim(q, start, block)
            kw = rep(jax.lax.dynamic_slice_in_dim(kx, start, w + block))
            vw = rep(jax.lax.dynamic_slice_in_dim(vx, start, w + block))
            qpos = start + jnp.arange(block)
            kpos = start - w + jnp.arange(w + block)
            exact = ((kpos[None] <= qpos[:, None]) & (kpos[None] > qpos[:, None] - w)
                     & (kpos[None] >= 0))
            seen = last[None, :] <= qpos[:, None] - w
            scores = jnp.concatenate(
                [jnp.einsum("qhd,khd->hqk", qb, rep(ks)),
                 jnp.einsum("qhd,khd->hqk", qb, kw)], axis=-1) * d ** -0.5
            p = jax.nn.softmax(jnp.where(
                jnp.concatenate([seen, exact], -1)[None], scores, -1e30), -1)
            m = padded // c
            return (jnp.einsum("hqk,khd->qhd", p[..., :m], rep(vs))
                    + jnp.einsum("hqk,khd->qhd", p[..., m:], vw))

        out = jax.lax.map(one, jnp.arange(0, padded, block))
        return out.reshape(padded, heads, d)[:n]

    return eva_attention


def _bf16_reference(run):
    """The reference computed in the nearest precision below the one the
    configuration states: every weight, activation, sum and statistic
    bfloat16 (products at the default precision)."""
    load = run.load_module

    def patched(kind, name):
        mod = load(kind, name)
        if kind == "references" and name == "eva_dense_decoder":
            import contextlib

            import jax
            import jax.numpy as jnp

            mod._f32 = lambda a: a.astype(jnp.bfloat16)
            rope, logits = mod._rope, mod.decoder_logits
            mod._rope = lambda x, theta: rope(x, theta).astype(x.dtype)
            mod.decoder_logits = lambda *a, **kw: logits(*a, **kw).astype(
                jnp.float32)
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
    if control == "sliding_reference":
        _sliding_reference(run)
    elif control == "bf16_reference":
        _bf16_reference(run)
    else:
        sys.path.insert(0, os.path.join(ROOT, "tests"))
        import test_eva_decoder as tests

        patch = types.SimpleNamespace(setattr=setattr)
        tests.CONTROLS[control](patch)
    return run.main(argv)


if __name__ == "__main__":
    sys.exit(main())
