"""nh_state_share_of_cache_pct — the recurrent states' share of the bytes live in the cache (Nemotron-H's keys).

The program's gauge ``arkflow_gen_kv_live_bytes`` carries a ``pool`` label,
named as ``cache_spec`` names the pools (``kv``: pages held x 16 x 2,048 B;
``ssm``: busy slots x 12,804,096 B, ``lib/costs_nemotron_h.slot_bytes``).
This reader: the ``ssm`` pool's bytes over all pools', in percent, the mean
of the window's two registry snapshots. What 6 blocks of 13 cost in memory:
as much as 6,252 tokens of the other two's K/V a slot, whatever the context.
Only on a configuration of one-mixer blocks (``ssm_state_share_of_cache_pct``
is Falcon-H1's).
"""

from benchmark.lib.costs_nemotron_h import sizes_of

NAME = "arkflow_gen_kv_live_bytes"


def _pools(snap) -> dict:
    pools: dict = {}
    for (name, labels), value in (snap or {}).items():
        if name == NAME:
            pool = dict(labels).get("pool")
            pools[pool] = pools.get(pool, 0.0) + float(value)
    return pools


def read(view):
    if sizes_of(view) is None:
        return None
    shares = []
    for snap in (getattr(view, "_open", None), getattr(view, "_close", None)):
        pools = _pools(snap)
        total = sum(pools.values())
        if "ssm" in pools and total > 0:
            shares.append(100.0 * pools["ssm"] / total)
    return sum(shares) / len(shares) if shares else None
