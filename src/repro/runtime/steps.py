"""Step functions: train_step / prefill_step / decode_step factories.

The train step is the device lowering of the outer farm skeleton:
  emitter   = batch sharding over (pod, data)
  workers   = SPMD model replicas (each internally a map/pipeline skeleton)
  collector = gradient reduction (reduce-scatter over data via FSDP
              shardings; all-reduce over pod, optionally int8-EF-compressed)
  feedback  = the optimizer update + grad-accumulation loop (wrap_around)
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..configs.base import Config
from ..core.plan import ShardingPlan
from ..models import params as pp
from ..models.lm import LM
from ..optim import clip_by_global_norm, ef_compress_grads, make_optimizer


# ---------------------------------------------------------------------------
def make_model(cfg: Config) -> LM:
    return LM(cfg)


def state_defs(cfg: Config, plan: ShardingPlan):
    """ParamDef trees for params and optimizer state (for dry-run structs
    and checkpoint layouts)."""
    model = LM(cfg)
    pdefs = model.param_defs()
    return pdefs


def init_state(cfg: Config, plan: ShardingPlan, key, optimizer=None):
    model = LM(cfg)
    opt = optimizer or make_optimizer(cfg.optimizer)
    params = model.init(key)
    return {"params": params, "opt": opt.init(params),
            "step": jnp.zeros((), jnp.int32)}


def state_shardings(cfg: Config, plan: ShardingPlan, optimizer=None):
    """NamedShardings for the full train state (params + opt + step)."""
    model = LM(cfg)
    opt = optimizer or make_optimizer(cfg.optimizer)
    pdefs = model.param_defs()
    p_sh = pp.shardings(pdefs, plan)
    ax_tree = opt.state_axes(pdefs)
    rep = NamedSharding(plan.mesh, P())

    def ax_to_sh(ax):
        if ax == () or ax is None:
            return rep
        return NamedSharding(plan.mesh, plan.param_spec(ax))  # shapes match params
    o_sh = jax.tree.map(ax_to_sh, ax_tree,
                        is_leaf=lambda x: isinstance(x, tuple))
    return {"params": p_sh, "opt": o_sh, "step": rep}


def state_structs(cfg: Config, plan: ShardingPlan, optimizer=None):
    """ShapeDtypeStructs for the train state — dry-run stand-ins."""
    model = LM(cfg)
    opt = optimizer or make_optimizer(cfg.optimizer)
    pdefs = model.param_defs()
    p_st = pp.shape_structs(pdefs, plan)

    ax_tree = opt.state_axes(pdefs)
    flat_defs = jax.tree.leaves(pdefs, is_leaf=pp.is_def)

    # build opt-state structs by pairing each param def with its state axes
    def build(defs, axes):
        if isinstance(axes, tuple):   # leaf: logical axes of a state tensor
            raise AssertionError
        return None

    def opt_struct(adef_ax, shape):
        return jax.ShapeDtypeStruct(shape, jnp.float32,
                                    sharding=plan.sharding_for(adef_ax, shape))

    if cfg.optimizer == "adamw":
        mk = lambda d: jax.ShapeDtypeStruct(
            d.shape, jnp.float32, sharding=plan.sharding_for(d.axes, d.shape))
        o_st = {"m": jax.tree.map(mk, pdefs, is_leaf=pp.is_def),
                "v": jax.tree.map(mk, pdefs, is_leaf=pp.is_def),
                "count": jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=plan.sharding_for(()))}
    else:
        def mk(d):
            sh, ax = d.shape, tuple(d.axes)
            if len(sh) >= 2 and sh[-1] >= 128 and sh[-2] >= 128:
                return {"vr": opt_struct(ax[:-1], sh[:-1]),
                        "vc": opt_struct(ax[:-2] + ax[-1:], sh[:-2] + sh[-1:])}
            return {"v": opt_struct(ax, sh)}
        o_st = {"s": jax.tree.map(mk, pdefs, is_leaf=pp.is_def),
                "count": jax.ShapeDtypeStruct((), jnp.int32,
                                              sharding=plan.sharding_for(()))}
    step = jax.ShapeDtypeStruct((), jnp.int32, sharding=plan.sharding_for(()))
    return {"params": p_st, "opt": o_st, "step": step}


# ---------------------------------------------------------------------------
def make_train_step(cfg: Config, plan: ShardingPlan, lr_fn: Callable,
                    optimizer=None, n_micro: Optional[int] = None,
                    max_grad_norm: float = 1.0,
                    compress_pod_grads: bool = False):
    """Returns train_step(state, batch) -> (state, metrics)."""
    model = LM(cfg)
    opt = optimizer or make_optimizer(cfg.optimizer)
    n_micro = n_micro or cfg.n_microbatches

    def loss_fn(params, batch):
        loss, metrics = model.loss(params, batch, plan)
        return loss, metrics

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def train_step(state, batch):
        params = state["params"]
        if n_micro > 1:
            micro = jax.tree.map(
                lambda t: t.reshape((n_micro, t.shape[0] // n_micro)
                                    + t.shape[1:]), batch)

            def body(carry, mb):
                acc, loss_acc = carry
                loss, metrics, grads = grads_of(params, mb)
                acc = jax.tree.map(
                    lambda a, g: a + g.astype(jnp.float32), acc, grads)
                return (acc, loss_acc + loss), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (gsum, loss_sum), _ = lax.scan(body, (zeros, 0.0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, gsum)
            loss = loss_sum / n_micro
            metrics = {}
        else:
            loss, metrics, grads = grads_of(params, batch)

        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        lr = lr_fn(state["step"])
        new_params, new_opt = opt.update(grads, state["opt"], params, lr)
        new_state = {"params": new_params, "opt": new_opt,
                     "step": state["step"] + 1}
        metrics = dict(metrics or {})
        metrics.update({"loss": loss, "grad_norm": gnorm, "lr": lr})
        return new_state, metrics

    return train_step


def make_prefill_step(cfg: Config, plan: ShardingPlan, cache_len: int,
                      stats: bool = False):
    """prefill_step(params, batch) -> (logits, caches), and the step's
    counts (``LM.prefill``) last with ``stats``."""
    model = LM(cfg)

    def prefill_step(params, batch):
        return model.prefill(params, batch, plan, cache_len=cache_len,
                             stats=stats)

    return prefill_step


def make_decode_step(cfg: Config, plan: ShardingPlan, cache_len: int,
                     stats: bool = False):
    """decode_step(params, caches, batch) -> (next tokens (B, 1), logits,
    caches), and the step's counts (``LM.decode_step``) last with
    ``stats``."""
    model = LM(cfg)
    cfg.cache_len = (min(cache_len, cfg.window) if cfg.attn_kind == "swa"
                     else cache_len)

    def decode_step(params, caches, batch):
        out = model.decode_step(params, caches, batch, plan, stats=stats)
        # greedy token for the feedback loop (argmax over vocab-sharded dim)
        next_tok = jnp.argmax(out[0][:, -1, :], axis=-1).astype(jnp.int32)
        return (next_tok[:, None],) + out

    return decode_step
