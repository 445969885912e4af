"""Step functions: train (allreduce | ADMM-consensus), prefill, decode.

``mode="allreduce"`` is the standard FSDP+TP data-parallel step (gradient
averaging happens implicitly through GSPMD sharding propagation).

``mode="admm"`` integrates the paper's technique (DESIGN.md §3): each
``data``-axis group keeps a LOCAL parameter replica; groups exchange
*decision variables* (parameters, never gradients/data) on a ring via
``ppermute`` and apply the Prop.-1 dual update (repro.core.consensus).
Implemented with ``jax.shard_map(axis_names={"data"})`` so the ``model``
(and ``pod``) axes stay auto-sharded by GSPMD inside each node.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.configs.base import InputShape, ModelConfig
from repro.core import consensus as consensus_lib
from repro.models import model as model_lib
from repro.models import transformer
from repro.optim import adamw, apply_updates, clip_by_global_norm

Params = Any


# ===========================================================================
# standard (allreduce) training
# ===========================================================================
def make_optimizer(lr: float = 3e-4, weight_decay: float = 0.01):
    return adamw(lr, weight_decay=weight_decay)


def make_train_state(cfg: ModelConfig, rng, shape: InputShape = None,
                     lr: float = 3e-4):
    params = model_lib.init_params(cfg, rng, shape)
    opt = make_optimizer(lr)
    return {"params": params, "opt": opt.init(params)}


def train_state_specs(cfg: ModelConfig, shape: InputShape = None):
    return jax.eval_shape(
        lambda k: make_train_state(cfg, k, shape), jax.random.key(0))


def make_train_step(cfg: ModelConfig, lr: float = 3e-4,
                    long_mode: bool = False, clip: float = 1.0,
                    microbatch: int = 0, grad_specs=None):
    """``microbatch > 0`` splits the global batch into that many chunks and
    accumulates gradients over a lax.scan — the classic activation-memory
    lever (§Perf): peak activation footprint drops ~microbatch-fold for an
    extra optimizer-latency trade.

    ``grad_specs`` (a PartitionSpec pytree matching params) constrains the
    gradients to the parameter sharding right after autodiff — this nudges
    GSPMD to emit reduce-scatters for FSDP weight grads instead of
    all-reduce+slice (§Perf pair-2 lever)."""
    opt = make_optimizer(lr)

    def loss_fn(params, batch):
        _, loss = transformer.forward_train(params, batch, cfg,
                                            long_mode=long_mode)
        return loss

    def train_step(state, batch):
        if microbatch > 1:
            B = batch["tokens"].shape[0]
            assert B % microbatch == 0, (B, microbatch)
            chunks = jax.tree.map(
                lambda x: x.reshape((microbatch, B // microbatch)
                                    + x.shape[1:]), batch)

            def acc_body(carry, mb):
                loss_acc, g_acc = carry
                loss, g = jax.value_and_grad(loss_fn)(state["params"], mb)
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(a.dtype), g_acc, g)
                return (loss_acc + loss, g_acc), None

            zeros = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), state["params"])
            (loss, grads), _ = jax.lax.scan(
                acc_body, (jnp.float32(0.0), zeros), chunks)
            loss = loss / microbatch
            grads = jax.tree.map(lambda g: g / microbatch, grads)
        else:
            loss, grads = jax.value_and_grad(loss_fn)(state["params"], batch)
        if grad_specs is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint,
                                 grads, grad_specs)
        grads, gnorm = clip_by_global_norm(grads, clip)
        updates, opt_state = opt.update(grads, state["opt"], state["params"])
        params = apply_updates(state["params"], updates)
        return ({"params": params, "opt": opt_state},
                {"loss": loss, "grad_norm": gnorm})

    return train_step


# ===========================================================================
# ADMM-consensus training (the paper's technique, generalized)
# ===========================================================================
class ConsensusTrainState(NamedTuple):
    params: Params           # leading axis R = data-axis size ("node" replicas)
    opt: Params
    dual: Params             # beta_v, same structure/leading axis
    step: jnp.ndarray


def make_consensus_train_state(cfg: ModelConfig, rng, mesh: Mesh,
                               shape: InputShape = None, lr: float = 3e-4):
    R = mesh.shape["data"]
    params = model_lib.init_params(cfg, rng, shape)
    opt = make_optimizer(lr)
    opt_state = opt.init(params)
    stack = lambda tree: jax.tree.map(
        lambda x: jnp.broadcast_to(x[None], (R,) + x.shape), tree)
    return ConsensusTrainState(
        params=stack(params),
        opt=stack(opt_state),
        dual=stack(jax.tree.map(
            lambda p: jnp.zeros_like(p, jnp.float32), params)),
        step=jnp.zeros((), jnp.int32))


def consensus_state_specs(cfg: ModelConfig, mesh: Mesh,
                          shape: InputShape = None):
    return jax.eval_shape(
        lambda k: make_consensus_train_state(cfg, k, mesh, shape),
        jax.random.key(0))


def make_consensus_train_step(cfg: ModelConfig, mesh: Mesh,
                              ccfg: consensus_lib.ConsensusConfig = None,
                              lr: float = 3e-4, long_mode: bool = False,
                              clip: float = 1.0, batch_spec: P = None):
    """Returns a step over (ConsensusTrainState, batch).

    State pytrees carry a leading replica axis sharded over ``data``;
    inside the shard_map each node sees its own replica and ONLY exchanges
    parameters with ring neighbors (collective_permute).
    """
    ccfg = ccfg or consensus_lib.ConsensusConfig()
    opt = make_optimizer(lr)
    axis = ccfg.axis
    if batch_spec is None:
        batch_spec = P(axis)

    def local_step(state: ConsensusTrainState, batch):
        # local shards: every leaf carries a leading replica axis (1, ...)
        params = jax.tree.map(lambda x: x[0], state.params)
        opt_state = jax.tree.map(lambda x: x[0], state.opt)
        dual = jax.tree.map(lambda x: x[0], state.dual)

        def loss_fn(p):
            _, loss = transformer.forward_train(p, batch, cfg,
                                                long_mode=long_mode)
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads, gnorm = clip_by_global_norm(grads, clip)

        cstate = consensus_lib.ConsensusState(dual=dual, step=state.step)
        do_exchange = (state.step % ccfg.every) == 0

        def with_exchange(args):
            grads, params, cstate = args
            return consensus_lib.consensus_round(grads, params, cstate, ccfg)

        def without(args):
            grads, params, cstate = args
            return grads, consensus_lib.ConsensusState(
                dual=cstate.dual, step=cstate.step + 1)

        if ccfg.every <= 1:
            grads, cstate = with_exchange((grads, params, cstate))
        else:
            grads, cstate = jax.lax.cond(do_exchange, with_exchange,
                                         without, (grads, params, cstate))

        updates, opt_state = opt.update(grads, opt_state, params)
        params = apply_updates(params, updates)

        loss_g = jax.lax.pmean(loss, axis)
        gap = consensus_lib.consensus_gap(params, axis)
        unsq = lambda tree: jax.tree.map(lambda x: x[None], tree)
        new_state = ConsensusTrainState(
            params=unsq(params),
            opt=unsq(opt_state),
            dual=unsq(cstate.dual),
            step=state.step + 1)
        return new_state, {"loss": loss_g, "grad_norm": gnorm,
                           "consensus_gap": gap}

    def train_step(state: ConsensusTrainState, batch):
        st_spec = ConsensusTrainState(params=P(axis), opt=P(axis),
                                      dual=P(axis), step=P())
        metric_spec = {"loss": P(), "grad_norm": P(),
                       "consensus_gap": P()}
        fn = jax.shard_map(
            local_step, mesh=mesh,
            in_specs=(st_spec, batch_spec),
            out_specs=(st_spec, metric_spec),
            axis_names={axis}, check_vma=False)
        return fn(state, batch)

    # jit-of-shard_map is the canonical form: eager shard_map dispatch
    # cannot reshard inputs that live on auto axes
    return jax.jit(train_step, donate_argnums=(0,))


# ===========================================================================
# serving steps
# ===========================================================================
def make_prefill_step(cfg: ModelConfig, long_mode: bool = False):
    def prefill_step(params, batch):
        return transformer.prefill(params, batch, cfg, long_mode=long_mode)
    return prefill_step


def make_decode_step(cfg: ModelConfig, long_mode: bool = False):
    def decode_step(params, tokens, cache, cache_index):
        logits, new_cache = transformer.decode(
            params, {"tokens": tokens}, cache, cache_index, cfg,
            long_mode=long_mode)
        return logits, new_cache, cache_index + 1
    return decode_step


def make_step(cfg: ModelConfig, shape: InputShape, **kw):
    """Step factory keyed on the workload's step kind."""
    long_mode = model_lib.use_long_mode(cfg, shape)
    if shape.step_kind == "train":
        return make_train_step(cfg, long_mode=long_mode, **kw)
    if shape.step_kind == "prefill":
        return make_prefill_step(cfg, long_mode=long_mode)
    if shape.step_kind == "decode":
        return make_decode_step(cfg, long_mode=long_mode)
    raise ValueError(shape.step_kind)
