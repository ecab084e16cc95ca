"""Step functions lowered by the dry-run and driven by train.py/serve.py.

  train_step   — first-order Adam LM training (the substrate baseline)
  vfl_zoo_step — the PAPER's technique at framework scale: party towers +
                 backbone, AsyREVEL block-coordinate ZO updates
  prefill_step — full-sequence forward (inference prefill)
  serve_step   — ONE new token against a KV cache / SSM state
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, VFLConfig
from repro.core import asyrevel
from repro.core.exchange import ZOExchange
from repro.core.vfl import TransformerVFLModel
from repro.models.model import Model
from repro.optim.optimizers import adam_init, adam_update


class TrainState(NamedTuple):
    params: dict
    opt: dict
    step: jnp.ndarray


def make_train_state(model: Model, key,
                     state_dtype=jnp.float32) -> TrainState:
    """``state_dtype=jnp.bfloat16`` stores the Adam moments quantized
    (half the optimizer memory; f32 master arithmetic every step —
    optim/optimizers.py)."""
    params = model.init(key)
    return TrainState(params, adam_init(params, state_dtype),
                      jnp.zeros((), jnp.int32))


def make_train_step(model: Model, schedule=None, grad_clip: float = 1.0,
                    microbatches: int = 1):
    """First-order Adam step. microbatches > 1 scans gradient accumulation
    over batch slices — peak activation memory drops ~1/microbatches at
    the same math (the fix for global-batch train shapes that exceed HBM;
    EXPERIMENTS.md §Perf extensions)."""
    sched = schedule or (lambda s: 3e-4)

    def grads_of(params, batch):
        def loss_fn(p):
            loss, metrics = model.loss(p, batch)
            return loss, metrics
        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state: TrainState, batch):
        if microbatches == 1:
            (loss, metrics), grads = grads_of(state.params, batch)
        else:
            mb = jax.tree.map(
                lambda a: a.reshape((microbatches,
                                     a.shape[0] // microbatches)
                                    + a.shape[1:]), batch)

            def body(acc, b):
                (loss_i, metrics_i), g_i = grads_of(state.params, b)
                acc_g, acc_l = acc
                acc_g = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32) / microbatches,
                    acc_g, g_i)
                return (acc_g, acc_l + loss_i / microbatches), metrics_i

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
            (grads, loss), metrics_all = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), mb)
            metrics = jax.tree.map(lambda a: a[-1], metrics_all)
        params, opt = adam_update(state.params, grads, state.opt,
                                  sched(state.step), grad_clip=grad_clip)
        return TrainState(params, opt, state.step + 1), (loss, metrics)

    return train_step


def make_prefill_step(model: Model):
    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch)
        return logits
    return prefill_step


def make_serve_step(model: Model):
    def serve_step(params, cache, token, pos):
        return model.decode_step(params, cache, token, pos)
    return serve_step


def make_vfl_zoo_step(model: Model, vfl: VFLConfig, codec: str | None = None,
                      mesh=None, data_axis: str = "data"):
    """The paper's AsyREVEL iteration wrapping this architecture as F_0.

    The two-point message round routes through one shared
    core/exchange.py ZOExchange; `codec` (default: vfl.codec) picks the
    up-link payload format for the c values (f32 | bf16 | int8).

    With `mesh`, the returned step is the sharded scale path: the batch
    shards over the mesh's `data` axis (leading batch dim, replicated
    when indivisible), the server loss psum-reduces to the global batch
    mean, and party/server state replicates — bit-identical to the
    unsharded step on a 1-device mesh (docs/scale.md)."""
    if codec is not None:
        vfl = dataclasses.replace(vfl, codec=codec)
    vm = TransformerVFLModel(model, vfl)
    ex = ZOExchange.from_config(vfl)

    def init(key):
        return asyrevel.init_state(vm, vfl, key)

    if mesh is None:
        def step(state, batch):
            return asyrevel.asyrevel_step(vm, vfl, state, batch, ex)
        return vm, init, step

    from repro.sharding.ctx import suspend_constraints
    from repro.sharding.rules import batch_pspecs, replicated_pspecs

    pm, ex_sharded, _ = asyrevel.shard_wrap(vm, ex, mesh, data_axis)

    def body(state, batch):
        with suspend_constraints():
            return asyrevel.asyrevel_step(pm, vfl, state, batch, ex_sharded)

    def step(state, batch):
        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(replicated_pspecs(state),
                      batch_pspecs(batch, mesh, batch_axes=(data_axis,))),
            out_specs=(replicated_pspecs(state), jax.sharding.PartitionSpec()),
            check_vma=False)(state, batch)

    return vm, init, step
