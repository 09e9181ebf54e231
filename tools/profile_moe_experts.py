"""Time the expert product alone, at the shapes the routed cells' chunks serve.

The kernel-alone microbench behind PERF.md's numbers for ``ops/moe_experts.py``
and ``ops/moe_grouped.py`` (PR 52): ``moe_expert_swiglu`` on a chunk's rows
(512, or LFM2's 256) and on a decode step's lanes, at each routed cell's
widths and held share, under a seeded uniform router over the model's
PUBLISHED expert count cut to the experts this chip holds (so most rows of
an ``ep`` cut carry few held experts, as served), the shared experts' columns
at weight 1. One JSON line a point:

- ``us_per_call``: best of ``--rounds`` timed loops of ``--reps`` calls
  (``lax.fori_loop`` inside ONE jitted program over a 2-layer stack, the
  layer alternating and the rows carried: the host's dispatch is paid once a
  loop);
- ``hit``: experts the call hit; ``pairs``: (row, expert) pairs routed;
- ``read_us``: the hit experts' bytes over the chip's HBM peak
  (``benchmark/peaks.json``): what one read of each takes;
- ``max_abs_err``: against ``expert_swiglu_dense`` over the hit experts at a
  sample of rows (``--check``).

    python tools/profile_moe_experts.py
    python tools/profile_moe_experts.py --cell kexaone_l5 --root /path/to/parent
    python tools/profile_moe_experts.py --cell kanana2_l6 --points 16:62,128:42,144:78

``--points rows:hit,...`` times the cell's widths at row counts of one's own
under a router held to ``hit`` of the experts (a block of a fused step: 16
lanes and a 128-token chunk are 144 rows, above one token tile, and hit what
either part hits), in place of ``--rows``.

``--root`` imports ``arkflow_tpu`` from another checkout (a parent commit
unpacked beside this one): run both in one call to compare on the same chip.
Needs a TPU (``--interpret`` rehearses tiny shapes on the CPU; its lines say
``"rehearsal"``).
"""

from __future__ import annotations

import argparse
import json
import sys
import time

HERE = __file__.rsplit("/", 2)[0]

#: cell -> (chunk rows, decode lanes, held routed, shared, published experts,
#: top-k, hidden, expert width)
CELLS = {
    "kanana2_l6": (128, 16, 128, 2, 128, 6, 2048, 768),
    "dots3_l5": (512, 32, 32, 1, 256, 8, 5120, 1536),
    "kexaone_l5": (512, 48, 16, 1, 128, 8, 6144, 2048),
    "mimo_l7": (512, 64, 16, 0, 256, 8, 4096, 2048),
    "lfm2_l12": (256, 128, 32, 0, 32, 4, 2048, 1792),
    "qwen3next_l8": (512, 128, 64, 1, 512, 10, 2048, 512),
}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cell", default="all", choices=["all", *CELLS])
    ap.add_argument("--rows", default="chunk", choices=["chunk", "decode", "both"])
    ap.add_argument("--points", default="", help="rows:hit,... (in place of --rows)")
    ap.add_argument("--root", default=HERE, help="checkout to import arkflow_tpu from")
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--check", action="store_true")
    ap.add_argument("--interpret", action="store_true")
    args = ap.parse_args()
    sys.path.insert(0, args.root)

    import jax
    import jax.numpy as jnp

    from arkflow_tpu.ops.moe_experts import expert_swiglu_dense, moe_expert_swiglu

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.interpret:
        print("found no TPU: a time from the CPU says nothing (--interpret "
              "rehearses tiny shapes)", file=sys.stderr)
        return 1
    hbm = None
    if device.platform == "tpu":
        with open(HERE + "/benchmark/peaks.json") as f:
            hbm = json.load(f)[device.device_kind]["hbm_bytes_per_s"]

    for cell in (CELLS if args.cell == "all" else [args.cell]):
        chunk, lanes, held, shared, published, k, d, f = CELLS[cell]
        if args.interpret:
            d, f = 64, 128
        e = held + shared
        keys = iter(jax.random.split(jax.random.PRNGKey(args.seed), 64))
        wg, wu = (jax.random.normal(next(keys), (2, e, d, f), jnp.bfloat16) / 8
                  for _ in "gu")
        wd = jax.random.normal(next(keys), (2, e, f, d), jnp.bfloat16) / 16
        points = [(t, published) for t in {
            "chunk": [chunk], "decode": [lanes], "both": [lanes, chunk]}[args.rows]]
        if args.points:
            points = [tuple(map(int, p.split(":"))) for p in args.points.split(",")]
        for t, among in points:
            x = jax.random.normal(jax.random.fold_in(next(keys), t), (t, d), jnp.bfloat16)
            # a uniform router over the first ``among`` experts
            draw = jax.random.uniform(jax.random.fold_in(next(keys), among),
                                      (t, published))
            chosen = jnp.argsort(jnp.where(jnp.arange(published) < among, draw, 2.0),
                                 axis=-1)[:, :k]
            cw = jax.nn.one_hot(chosen, published, dtype=jnp.float32).sum(1)[:, :held] / k
            cw = jnp.concatenate([cw, jnp.ones((t, shared))], axis=-1)

            @jax.jit
            def timed(x, cw, wg, wu, wd, reps):
                def body(i, x):
                    y = moe_expert_swiglu(x, cw, wg, wu, wd, i % 2,
                                          interpret=args.interpret)
                    return (x + y * 0.01).astype(x.dtype)
                return jax.lax.fori_loop(0, reps, body, x)

            jax.block_until_ready(timed(x, cw, wg, wu, wd, args.reps))
            best = float("inf")
            for _ in range(args.rounds):
                t0 = time.perf_counter()
                jax.block_until_ready(timed(x, cw, wg, wu, wd, args.reps))
                best = min(best, time.perf_counter() - t0)
            hit = int((cw != 0).any(axis=0).sum())
            line = {"cell": cell, "rows": t, "hidden": d, "width": f, "experts": e,
                    "hit": hit, "pairs": int((cw != 0).sum()),
                    "us_per_call": round(best / args.reps * 1e6, 1),
                    "root": args.root, "device": device.device_kind}
            if hbm:
                line["read_us"] = round(hit * 3 * d * f * 2 / hbm * 1e6, 1)
            if args.interpret:
                line["rehearsal"] = True
            if args.check:
                got = moe_expert_swiglu(x, cw, wg, wu, wd, 1, interpret=args.interpret)
                want = expert_swiglu_dense(x[:64], cw[:64], wg[1], wu[1], wd[1])
                line["max_abs_err"] = float(jnp.abs(
                    got[:64].astype(jnp.float32) - want.astype(jnp.float32)).max())
                line["max_abs"] = float(jnp.abs(want.astype(jnp.float32)).max())
            print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
