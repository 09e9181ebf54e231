"""ssm_state_share_of_cache_pct — the recurrent states' share of the bytes live in the cache.

The harness samples the gauge ``arkflow_gen_kv_live_bytes`` summed over its
pools (``kv`` + ``ssm``, ``tpu/serving.py::_update_gauges``). The state
pool's part is the busy slots (gauge ``arkflow_gen_slots_busy``) times what a
slot holds (``lib/costs_hybrid_ssm.slot_bytes``: the float32 state and the
conv window over all layers, whatever the context); the rest is K/V pages
held. A state costs a slot as much as 2,048 tokens of K/V a layer: at short
contexts it is most of the cache.
"""

from benchmark.lib.costs_hybrid_ssm import mixer_of, slot_bytes


def read(view):
    live = view.gauge("arkflow_gen_kv_live_bytes")
    busy = view.gauge("arkflow_gen_slots_busy")
    mixer = mixer_of(view)
    if not live or not busy or mixer is None:
        return None
    total = sum(live) / len(live)
    state = sum(busy) / len(busy) * slot_bytes(
        layers=view.sizes["num_hidden_layers"], **mixer)
    return None if total <= 0 else 100.0 * state / total
