"""train_step / serve_step builders — what the launcher jits and the
multi-pod dry-run lowers.

Composition per step:
  1. (optional) gradient accumulation: lax.scan over microbatches;
  2. loss/grad of the model's train_loss (remat per layer-group inside);
  3. (optional, multi-pod) int8 inter-pod gradient exchange with error
     feedback: grads are reduced across 'data'/'model' by autodiff as usual,
     while the 'pod' axis is kept *manual* (shard_map auto-mode) so the
     exchange really moves 1 byte/param over the slow cross-pod links —
     2 pods exchange via collective_permute(int8) and combine locally;
  4. AdamW update with ZeRO-1-sharded optimizer state.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import TrainConfig
from repro.models.lm import LM
from repro.train.optimizer import TrainState, adamw_update


def _split_microbatches(batch, n):
    return jax.tree.map(
        lambda x: x.reshape((n, x.shape[0] // n) + x.shape[1:]), batch)


def make_loss_fn(model: LM):
    def loss_fn(params, batch):
        loss, metrics = model.train_loss(params, batch, remat=True)
        return loss, metrics
    return loss_fn


def _int8_pod_exchange(grads, ef, npods: int):
    """Quantized inter-pod all-reduce with error feedback (manual 'pod' axis).

    Wire format is int8 (1 byte/param/hop on the inter-pod links); each hop
    dequantizes and re-accumulates locally, so precision loss is bounded by
    the error-feedback residual carried to the next step.
    """
    def one(g, e):
        g = g.astype(jnp.float32) + e
        scale = jnp.maximum(jnp.max(jnp.abs(g)) / 127.0, 1e-12)
        q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
        new_e = g - q.astype(jnp.float32) * scale
        total = q.astype(jnp.float32) * scale
        for hop in range(1, npods):
            perm = [(i, (i + hop) % npods) for i in range(npods)]
            q_peer = jax.lax.ppermute(q, "pod", perm)
            s_peer = jax.lax.ppermute(scale, "pod", perm)
            total = total + q_peer.astype(jnp.float32) * s_peer
        return total / npods, new_e

    out = jax.tree.map(one, grads, ef)
    g = jax.tree.map(lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple))
    e = jax.tree.map(lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple))
    return g, e


def make_train_step(model: LM, tcfg: TrainConfig, *, mesh=None):
    """Returns train_step(state, batch) -> (state, metrics)."""
    loss_fn = make_loss_fn(model)
    npods = mesh.shape.get("pod", 1) if mesh is not None else 1
    use_compress = tcfg.grad_compression == "int8" and npods > 1

    def grads_of(params, batch):
        if tcfg.microbatch and tcfg.microbatch > 1:
            mbs = _split_microbatches(batch, tcfg.microbatch)

            def acc_body(carry, mb):
                g_acc, l_acc = carry
                (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + loss), None

            g0 = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                              params)
            (g, loss), _ = jax.lax.scan(acc_body, (g0, jnp.float32(0.0)), mbs)
            inv = 1.0 / tcfg.microbatch
            return jax.tree.map(lambda x: x * inv, g), loss * inv
        (loss, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, batch)
        return g, loss

    def plain_step(state: TrainState, batch):
        g, loss = grads_of(state.params, batch)
        new_state = adamw_update(tcfg, state, g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for x in jax.tree.leaves(g)))
        return new_state, {"loss": loss, "gnorm": gnorm,
                           "step": new_state.step}

    if not use_compress:
        return plain_step

    # ---- multi-pod int8 gradient exchange (manual 'pod' axis) -------------
    def pod_step(state: TrainState, batch):
        g, loss = grads_of(state.params, batch)
        g, new_ef = _int8_pod_exchange(g, state.ef, npods)
        loss = jax.lax.pmean(loss, "pod")
        new_state = adamw_update(tcfg, dataclasses.replace(state, ef=new_ef), g)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                             for x in jax.tree.leaves(g)))
        return new_state, {"loss": loss, "gnorm": gnorm,
                           "step": new_state.step}

    def wrapped(state, batch):
        # manualize ONLY the 'pod' axis (data/model stay GSPMD-auto inside):
        # state replicated across pods, batch sharded on the leading dim.
        fn = shard_map(
            pod_step, mesh=mesh,
            in_specs=(P(), P("pod")),
            out_specs=(P(), P()),
            check_vma=False,
            axis_names=frozenset({"pod"}))
        return fn(state, batch)

    return wrapped
