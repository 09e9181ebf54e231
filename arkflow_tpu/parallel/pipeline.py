"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pp`` axis.

Completes the parallelism family (dp/tp/sp/ep/pp): the decoder's stacked
layer parameters shard along their leading (layer) dimension over ``pp``
stages, and activations stream stage-to-stage with ``jax.lax.ppermute``
inside a ``shard_map`` — the TPU-native expression of pipeline parallelism
(a ring of ICI hops, no NCCL-style send/recv). The classic GPipe schedule
runs M microbatches over ``M + S - 1`` ticks, so all S stages are busy in
the steady state and the bubble is (S-1)/(M+S-1).

Scope: dense decoder configs (MoE routes through ep, long context through
sp/ring attention — composing those with pp is future work; the builder
rejects the combinations). dp composes: the batch shards over ``dp`` while
each dp-replica's pipeline runs over ``pp``.

Correctness bar (tested): pp loss == single-device loss to float tolerance,
and grads flow to every stage's parameters (embedding/head replicate; their
grads psum across stages via the shard_map transpose).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from arkflow_tpu.errors import ConfigError
from arkflow_tpu.models import common as cm
from arkflow_tpu.models.decoder import DecoderConfig, _attention_block, _mlp


def pp_param_specs(cfg: DecoderConfig) -> dict:
    """Layer stacks shard over pp on the layer dim; the rest replicates."""
    layer = {
        "attn_norm": {"scale": P("pp")},
        "wq": {"w": P("pp")}, "wk": {"w": P("pp")}, "wv": {"w": P("pp")},
        "wo": {"w": P("pp")},
        "mlp_norm": {"scale": P("pp")},
        "w_gate": {"w": P("pp")}, "w_up": {"w": P("pp")}, "w_down": {"w": P("pp")},
    }
    return {
        "embed": {"table": P()},
        "norm_out": {"scale": P()},
        "lm_head": {"w": P()},
        "layers": layer,
    }


def _stage_apply(lp_stack, x, cfg: DecoderConfig, positions, causal):
    """Run this stage's local layer stack (the shared dense block math)."""

    def layer(x, lp):
        x = _attention_block(lp, x, cfg, positions, causal)
        y = cm.rms_norm(lp["mlp_norm"], x, cfg.norm_eps)
        return x + _mlp(lp, y, cfg), None

    x, _ = jax.lax.scan(layer, x, lp_stack)
    return x


def make_pp_train_step(cfg: DecoderConfig, optimizer, mesh: Mesh, *,
                       microbatches: int | None = None):
    """Pipeline-parallel training step over mesh axes (dp, pp).

    Returns ``train_step(params, opt_state, batch) -> (params, opt_state,
    loss)``; jit it under the mesh. ``batch`` carries input_ids/targets/mask
    sharded over dp. Params must be placed with ``pp_param_specs`` (layer
    stacks split across stages).
    """
    if cfg.num_experts > 1:
        raise ConfigError("pipeline parallelism + MoE (ep) is not composed yet")
    if cfg.use_ring_attention:
        raise ConfigError("pipeline parallelism + ring attention is not composed yet")
    stages = mesh.shape["pp"]
    if cfg.layers % stages != 0:
        raise ConfigError(f"layers ({cfg.layers}) must divide by pp stages ({stages})")
    n_micro = microbatches or stages
    perm = [(i, (i + 1) % stages) for i in range(stages)]

    def pp_loss(params, ids, targets, mask):
        """Runs per-device under shard_map: layer stack is the LOCAL shard."""
        stage = jax.lax.axis_index("pp")
        b, s = ids.shape
        if b % n_micro != 0:
            raise ConfigError(
                f"per-replica batch {b} must divide by microbatches {n_micro}")
        mb = b // n_micro
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (mb, s))
        causal = jnp.tril(jnp.ones((s, s), bool))[None, None]

        # every stage embeds (params replicate; trivial FLOPs) — only stage
        # 0's result enters the pipeline, but a uniform program keeps SPMD
        x = cm.embedding(params["embed"], ids)                     # [B, S, D]
        mb_x = x.reshape(n_micro, mb, s, cfg.dim)

        def tick(cur, t):
            # stage 0 ingests microbatch t (clamped; ticks >= M recirculate
            # garbage that never reaches a valid output slot)
            inject = jax.lax.dynamic_index_in_dim(
                mb_x, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
            inp = jnp.where(stage == 0, inject, cur)
            out = _stage_apply(params["layers"], inp, cfg, positions, causal)
            nxt = jax.lax.ppermute(out, "pp", perm)
            return nxt, out

        zeros = jnp.zeros((mb, s, cfg.dim), x.dtype)
        _, outs = jax.lax.scan(tick, zeros, jnp.arange(n_micro + stages - 1))
        # the LAST stage's outputs at ticks S-1 .. S-1+M-1 are the finished
        # microbatches, in order
        final = outs[stages - 1:stages - 1 + n_micro]              # [M, mb, S, D]
        h = final.reshape(b, s, cfg.dim)
        h = cm.rms_norm(params["norm_out"], h, cfg.norm_eps)
        logits = cm.dense(params["lm_head"], h).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        ll = jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
        maskf = mask.astype(jnp.float32)
        local = -(ll * maskf).sum() / jnp.maximum(maskf.sum(), 1.0)
        # only the last stage computed real logits; broadcast its loss
        loss = jax.lax.psum(jnp.where(stage == stages - 1, local, 0.0), "pp")
        return jax.lax.pmean(loss, "dp")

    specs = pp_param_specs(cfg)
    data_spec = P("dp")
    loss_fn = jax.shard_map(
        pp_loss, mesh=mesh, in_specs=(specs, data_spec, data_spec, data_spec),
        out_specs=P(), check_vma=False)

    def train_step(params, opt_state, batch):
        import optax

        loss, grads = jax.value_and_grad(loss_fn)(
            params, batch["input_ids"], batch["targets"], batch["mask"])
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return train_step
