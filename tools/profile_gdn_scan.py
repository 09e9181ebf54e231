"""Time the gated delta rule's two kernels alone, at the shapes ``qwen3next_l8`` serves (``--mixer kda``: the per-channel delta rule's, ``ops/kda_scan.py``, at ``kimilinear_l8``'s).

The kernel-alone microbench behind PERF.md's numbers for ``ops/gdn_scan.py``
(PR 49): the decode update (``gdn_state_update``: 128 lanes, 32 heads of
128 x 128, one call a linear layer of a 6-layer float32 pool) and the chunk
scan (``gdn_chunk_scan``: one row's 512-token chunk) on the chip this process
holds, each checked against its plain form first. One JSON line a point:

- ``us_per_call``: best of ``--rounds`` timed loops of ``--reps`` calls
  (``lax.fori_loop`` over the pool's layers inside ONE jitted program, the
  pool carried and donated: the host's dispatch is paid once a loop);
- ``needed_mb`` / ``roof_pct``: the call's needed bytes (the rows' states in
  and out, the operands in, the output back: ``benchmark/lib/
  costs_gdn_gqa_moe``) and, for the scan, its needed operations, over the
  chip's peaks (``benchmark/peaks.json``) and the call's time;
- ``max_abs_err``: the kernel's outputs and states against the plain form's.

    python tools/profile_gdn_scan.py
    python tools/profile_gdn_scan.py --kind scan --chunk 256
    python tools/profile_gdn_scan.py --mixer kda      (a decay a key channel)

Needs a TPU (``--interpret`` rehearses tiny shapes on the CPU; its lines say
``"rehearsal"``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--kind", default="all", choices=["all", "update", "scan"])
    ap.add_argument("--mixer", default="gdn", choices=["gdn", "kda"],
                    help="gdn: one decay a head (ops/gdn_scan); kda: a decay a "
                         "key channel (ops/kda_scan), both at 32 heads of 128")
    ap.add_argument("--lanes", type=int, default=128)
    ap.add_argument("--chunk", type=int, default=512)
    ap.add_argument("--reps", type=int, default=12)
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from arkflow_tpu.ops import gdn_scan as gs
    from arkflow_tpu.ops import kda_scan as ks
    from benchmark.lib import costs_gdn_gqa_moe as costs
    from benchmark.lib import costs_kda_mla_moe as kda_costs

    kda = args.mixer == "kda"
    update = ks.kda_state_update if kda else gs.gdn_state_update
    scan = ks.kda_chunk_scan if kda else gs.gdn_chunk_scan

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        print("found no TPU: a time from the CPU says nothing (--interpret "
              "rehearses tiny shapes)", file=sys.stderr)
        return 1
    peaks = None
    if device.platform == "tpu":
        with open(__file__.rsplit("/", 2)[0] + "/benchmark/peaks.json") as f:
            peaks = json.load(f)[device.device_kind]
    layers, heads, dk, dv = (2, 4, 16, 128) if args.interpret else (6, 32, 128, 128)
    dv = dk if kda and args.interpret else dv   # a KDA head's widths are one
    lanes = 3 if args.interpret else args.lanes
    chunk = 70 if args.interpret else args.chunk
    keys = iter(jax.random.split(jax.random.PRNGKey(0), 16))
    pool = jax.random.normal(next(keys), (layers, lanes + 1, heads, dk, dv),
                             jnp.float32)
    unit = lambda x: x * jax.lax.rsqrt(  # noqa: E731
        jnp.square(x).sum(-1, keepdims=True) + 1e-6)

    def operands(b, t):
        q = unit(jax.random.normal(next(keys), (b, t, heads, dk))) * dk ** -0.5
        k = unit(jax.random.normal(next(keys), (b, t, heads, dk)))
        v = jax.random.normal(next(keys), (b, t, heads, dv))
        g = -jnp.exp(2 * jax.random.normal(
            next(keys), (b, t, heads) + ((dk,) if kda else ())) - 2)
        beta = jax.nn.sigmoid(jax.random.normal(next(keys), (b, t, heads)))
        return q, k, v, g, beta

    points = []
    if args.kind in ("all", "update"):
        ops = tuple(a[:, 0] for a in operands(lanes, 1))
        rows = 1 + jnp.arange(lanes, dtype=jnp.int32)
        points.append(("update", lambda st, layer, rows=rows, ops=ops, **kw:
                       update(st, layer, rows, *ops, **kw),
            costs.update_bytes(lanes=lanes, layers=1, value_heads=heads,
                               key_dim=dk, value_dim=dv, conv_channels=0, taps=1),
            0.0))
    if args.kind in ("all", "scan"):
        ops = operands(1, chunk)
        rows, fresh = jnp.asarray([1], jnp.int32), jnp.asarray([False])
        points.append(("scan", lambda st, layer, rows=rows, ops=ops, **kw:
                       scan(st, layer, rows, fresh, *ops, **kw),
            kda_costs.chunk_scan_bytes(tokens=chunk, layers=1, heads=heads,
                                       head_dim=dk) if kda else
            costs.chunk_scan_bytes(tokens=chunk, layers=1, value_heads=heads,
                                   key_dim=dk, value_dim=dv),
            kda_costs.chunk_scan_flops(tokens=chunk, layers=1, heads=heads,
                                       head_dim=dk) if kda else
            costs.chunk_scan_flops(tokens=chunk, layers=1, value_heads=heads,
                                   key_dim=dk, value_dim=dv)))
    kern = dict(kernel=True, interpret=args.interpret)
    for name, call, nbytes, flops in points:
        want_o, want_pool = jax.jit(lambda st: call(st, 1))(pool)
        got_o, got_pool = jax.jit(lambda st: call(st, 1, **kern))(pool)
        line = {"kernel": f"{args.mixer}_{name}", "lanes": lanes, "chunk": chunk, "heads": heads,
                "device": device.device_kind,
                "max_abs_err": float(max(jnp.abs(got_o - want_o).max(),
                                         jnp.abs(got_pool - want_pool).max()))}
        if args.interpret:
            line["rehearsal"] = True
        else:
            def many(st, reps):
                def body(i, carry):
                    st, acc = carry
                    o, st = call(st, i % layers, **kern)
                    return st, acc + o.sum()
                return jax.lax.fori_loop(0, reps, body, (st, jnp.zeros((), jnp.float32)))

            timed = jax.jit(many, static_argnums=1, donate_argnums=0)
            st, _ = jax.block_until_ready(timed(pool + 0, args.reps))
            best = float("inf")
            for _ in range(args.rounds):
                t0 = time.perf_counter()
                st, _ = jax.block_until_ready(timed(st, args.reps))
                best = min(best, time.perf_counter() - t0)
            us = best / args.reps * 1e6
            least = max(nbytes / peaks["hbm_bytes_per_s"],
                        flops / peaks["bf16_flops_per_s"])
            line.update(us_per_call=round(us, 1), needed_mb=round(nbytes / 1e6, 2),
                        needed_gflop=round(flops / 1e9, 3),
                        roof_pct=round(least / (us * 1e-6) * 100, 2))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
