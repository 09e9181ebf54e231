"""Time the hyper-connection mixing alone, both forms, at the rows ``xing4_l10`` serves.

The kernel-alone microbench behind PERF.md's numbers for ``ops/mhc_mix.py``
(PR 53): ``mhc_pre`` and ``mhc_post`` over 4 streams of 3,584 at 32 rows (a
decode step's lanes) and 512 rows (a prefill chunk), the Pallas kernel and
the plain-XLA form, on the chip this process holds, the kernel checked
against the plain form first. One JSON line a point:

- ``us_per_call``: best of ``--rounds`` timed loops of ``--reps`` calls
  (``lax.fori_loop`` inside ONE jitted program, each call's streams the call
  before's result and nothing else computed, so none is hoisted or dropped
  and the host's dispatch is paid once a loop): ``mhc_post`` alone, and a
  whole sub-layer's mixing (``mhc_pre``, its input handed on as the output,
  ``mhc_post``: ``us_of_both``); ``mhc_pre`` is the difference;
- ``needed_mb`` / ``roof_pct``: the call's needed bytes (``benchmark/lib/
  costs_mhc_mla_moe.mix_bytes``: the streams in, the sub-layer's input and
  the coefficients out — or the streams, the output and the coefficients in
  and the streams out) over the chip's HBM peak (``benchmark/peaks.json``)
  and the call's time;
- ``max_abs_err``: the kernel's outputs against the plain form's.

    python tools/profile_mhc_mix.py
    python tools/profile_mhc_mix.py --rows 32 --form xla

Needs a TPU (``--interpret`` rehearses tiny shapes on the CPU; its lines say
``"rehearsal"``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time
import types

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rows", type=int, nargs="*", default=[32, 512])
    ap.add_argument("--form", default="both", choices=["both", "kernel", "xla"])
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from arkflow_tpu.models import decoder as dec
    from arkflow_tpu.ops import mhc_mix as mm
    from benchmark.lib import costs_mhc_mla_moe as costs

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        print("found no TPU: a time from the CPU says nothing (--interpret "
              "rehearses tiny shapes)", file=sys.stderr)
        return 1
    peaks = None
    if device.platform == "tpu":
        with open(__file__.rsplit("/", 2)[0] + "/benchmark/peaks.json") as f:
            peaks = json.load(f)[device.device_kind]
    n, c = (4, 256) if args.interpret else (4, 3584)
    # the seeds a served model's sub-layer has (``_init_mhc`` reads two sizes)
    leaves = dec._init_mhc(jax.random.PRNGKey(0), types.SimpleNamespace(hc_mult=n, dim=c))
    kw = dict(n=n, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
    forms = [f for f in ("kernel", "xla") if args.form in ("both", f)]
    for rows in ([20] if args.interpret else args.rows):
        k1, k2 = jax.random.split(jax.random.PRNGKey(rows))
        x = (2 * jax.random.normal(k1, (1, rows, n * c))).astype(jnp.bfloat16)
        y = jax.random.normal(k2, (1, rows, c)).astype(jnp.bfloat16)
        want_u, want_h = jax.jit(lambda x: mm.mhc_pre_xla(x, leaves, **kw))(x)
        want_o = jax.jit(mm.mhc_post_xla)(x, y, want_h)
        got_u, got_h = jax.jit(lambda x: mm.mhc_pre_kernel(
            x, leaves, interpret=args.interpret, **kw))(x)
        got_o = jax.jit(lambda x, y, h: mm.mhc_post_kernel(
            x, y, h, interpret=args.interpret))(x, y, got_h)
        f32 = lambda a: a.astype(jnp.float32)  # noqa: E731
        errs = {"mhc_pre": float(max(jnp.abs(f32(got_u) - f32(want_u)).max(),
                                     jnp.abs(got_h[..., :want_h.shape[-1]] - want_h).max())),
                "mhc_post": float(jnp.abs(f32(got_o) - f32(want_o)).max())}
        half = costs.mix_bytes(tokens=rows, hidden=c, n=n, sub_layers=1)
        k = costs.n_coefficients(n)
        need = {"mhc_pre": rows * ((n + 1) * c * 2 + 4 * k)
                + costs.mix_leaves_bytes(hidden=c, n=n)}
        need["mhc_post"] = half - need["mhc_pre"]
        for form in forms:
            kern = form == "kernel"
            pre = lambda x: mm.mhc_pre(x, leaves, kernel=kern,  # noqa: E731
                                       interpret=args.interpret, **kw)
            post = lambda x, y, h: mm.mhc_post(x, y, h, kernel=kern,  # noqa: E731
                                               interpret=args.interpret)
            h0 = pre(x)[1]

            def both(x, pre=pre, post=post):
                u, h = pre(x)          # a sub-layer that hands its input on
                return post(x, u, h)

            # each call's streams are the last call's result and nothing
            # else is computed: a chain, nothing hoisted out of the loop
            calls = {"mhc_post": lambda x, post=post, h0=h0: post(x, y, h0),
                     "mhc_pre+mhc_post": both}
            times = {}
            for name, call in calls.items():
                if args.interpret:
                    jax.block_until_ready(jax.jit(call)(x))
                    continue
                timed = jax.jit(lambda x, reps, call=call: jax.lax.fori_loop(
                    0, reps, lambda i, x: call(x), x), static_argnums=1)
                jax.block_until_ready(timed(x, args.reps))
                best = float("inf")
                for _ in range(args.rounds):
                    t0 = time.perf_counter()
                    jax.block_until_ready(timed(x, args.reps))
                    best = min(best, time.perf_counter() - t0)
                times[name] = best / args.reps * 1e6
            for name in ("mhc_pre", "mhc_post"):
                line = {"kernel": name, "form": form, "rows": rows, "streams": n,
                        "hidden": c, "device": device.device_kind,
                        "max_abs_err": errs[name]}
                if args.interpret:
                    line["rehearsal"] = True
                else:
                    # mhc_pre: a whole sub-layer's mixing less its mhc_post
                    us = (times["mhc_post"] if name == "mhc_post"
                          else times["mhc_pre+mhc_post"] - times["mhc_post"])
                    line.update(
                        us_per_call=round(us, 1),
                        us_of_both=round(times["mhc_pre+mhc_post"], 1),
                        needed_mb=round(need[name] / 1e6, 3),
                        roof_pct=round(need[name] / peaks["hbm_bytes_per_s"]
                                       / (us * 1e-6) * 100, 2))
                print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
