"""gdn_state_share_of_cache_pct — the delta rule's states' share of the bytes live in the cache.

The program's gauge ``arkflow_gen_kv_live_bytes`` carries a ``pool`` label,
named as ``cache_spec`` names the pools (``kv``: pages held x 16 x 4,096 B;
``gdn``: busy slots x the per-slot pool's ``bytes_per_slot``, 12,877,824 B by
``lib/costs_gdn_gqa_moe.slot_bytes``). This reader: the ``gdn`` pool's bytes
over all pools', in percent, the mean of the window's two registry snapshots
(its opening and its close) — the harness's 50 ms samples sum a gauge over
its label sets, so the label survives only there. What six layers of eight
cost in memory: as much as 3,144 tokens of the other two's K/V a slot,
whatever the context. A program whose gauge has no ``gdn`` pool reads
nothing.
"""

NAME = "arkflow_gen_kv_live_bytes"


def _pools(snap) -> dict:
    pools: dict = {}
    for (name, labels), value in (snap or {}).items():
        if name == NAME:
            pool = dict(labels).get("pool")
            pools[pool] = pools.get(pool, 0.0) + float(value)
    return pools


def read(view):
    shares = []
    for snap in (getattr(view, "_open", None), getattr(view, "_close", None)):
        pools = _pools(snap)
        total = sum(pools.values())
        if "gdn" in pools and total > 0:
            shares.append(100.0 * pools["gdn"] / total)
    return sum(shares) / len(shares) if shares else None
