"""conv_state_share_of_cache_pct — the conv windows' share of the bytes live in the cache.

The program's gauge ``arkflow_gen_kv_live_bytes`` carries a ``pool`` label,
named as ``cache_spec`` names the pools (``kv``: pages held x 16 x 6,144 B;
``conv``: busy slots x the per-slot pool's ``bytes_per_slot``;
``tpu/serving.py::_update_gauges``). This reader: the ``conv`` pool's bytes
over all pools', in percent, the mean of the window's two registry snapshots
(its opening and its close) — the harness's 50 ms samples sum a gauge over
its label sets, so the label survives only there. What nine layers of twelve
cost in memory: by ``lib/costs_conv_gqa_moe.slot_bytes`` 73,728 B a slot,
as much as twelve tokens of the other three's K/V (about 1 %). A program
whose gauge has no ``conv`` pool reads nothing.
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
        if "conv" in pools and total > 0:
            shares.append(100.0 * pools["conv"] / total)
    return sum(shares) / len(shares) if shares else None
