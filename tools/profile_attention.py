"""On-chip A/B: ragged Pallas attention vs XLA attention at serving shapes.

Run on a reachable TPU backend (falls back to CPU with interpret=True for a
smoke check, but CPU timings are meaningless for the kernel decision):

    python tools/profile_attention.py

Prints one JSON line per (batch, seq, fill) point with median step times for
both implementations and the speedup. ``fill`` is the fraction of each
row's positions that are real tokens — the ragged kernel's win comes from
skipping fully-padded K tiles, so low fill favors Pallas. This justifies
(or refutes, per shape) the auto-on default in ModelRunner._resolve_auto_flags.
"""

from __future__ import annotations

import functools
import json
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main() -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from arkflow_tpu.models import common as cm
    from arkflow_tpu.ops.ragged_attention import ragged_flash_attention
    from arkflow_tpu.tpu.jaxcache import enable_persistent_cache

    enable_persistent_cache()
    dev = jax.devices()[0]
    on_tpu = dev.platform == "tpu" or "tpu" in getattr(dev, "device_kind", "").lower()
    interpret = not on_tpu
    print(f"# device: {dev} (interpret={interpret})", file=sys.stderr, flush=True)

    heads, dh = 12, 64
    shapes = [(32, 128), (8, 512), (4, 1024)] if on_tpu else [(2, 128)]
    fills = [1.0, 0.5, 0.25]
    reps = 30 if on_tpu else 3

    # scalar-reduced outputs + device_get sync: device_get of a scalar
    # forces the real wait with a negligible (4-byte) transfer
    def xla_attn(q, k, v, mask):
        return cm.attention(q, k, v, mask).astype(jnp.float32).sum()

    jx = jax.jit(xla_attn)

    @functools.partial(jax.jit, static_argnames=("interpret",))
    def pallas_attn(qh, kh, vh, lengths, interpret=False):
        return ragged_flash_attention(
            qh, kh, vh, lengths, interpret=interpret).astype(jnp.float32).sum()

    for b, s in shapes:
        rng = np.random.RandomState(0)
        q = jnp.asarray(rng.randn(b, s, heads, dh), jnp.bfloat16)
        k, v = q, q
        qh = jnp.einsum("bshd->bhsd", q)
        for fill in fills:
            lengths = jnp.full((b,), max(1, int(s * fill)), jnp.int32)
            mask = (jnp.arange(s)[None, :] < lengths[:, None])[:, None, None, :]

            def run_xla():
                return jax.device_get(jx(q, k, v, mask))

            def run_pallas():
                return jax.device_get(pallas_attn(qh, qh, qh, lengths,
                                                  interpret=interpret))

            run_xla(); run_pallas()  # compile
            tx = _median_ms(run_xla, reps)
            tp = _median_ms(run_pallas, reps)
            print(json.dumps({
                "batch": b, "seq": s, "fill": fill, "heads": heads, "dh": dh,
                "xla_ms": round(tx, 3), "pallas_ms": round(tp, 3),
                "pallas_speedup": round(tx / tp, 3) if tp > 0 else None,
            }), flush=True)


def _median_ms(fn, reps: int) -> float:
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        times.append((time.perf_counter() - t0) * 1000.0)
    times.sort()
    return times[len(times) // 2]


if __name__ == "__main__":
    main()
