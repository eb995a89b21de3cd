"""Generic train/serve step factories."""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from ..optim.optimizers import Optimizer, apply_updates
from .state import TrainState


def make_train_step(loss_fn: Callable, optimizer: Optimizer,
                    n_micro: int = 1) -> Callable:
    """loss_fn(params, batch) -> (loss, aux); aux may carry 'touched' masks
    which are merged into the state's incremental-checkpoint tracker.

    ``n_micro > 1`` enables gradient accumulation over micro-batches (scan) —
    activation memory scales 1/n_micro while the gradient buffer is one
    params-sized f32 tree (sharded like the params).

    Named scopes mark the step's phases in a profile: ``backward`` (the
    loss and its gradient; the model's forward scopes nest inside it) and
    ``update`` (the optimizer over every parameter, embedding rows
    included)."""

    def grads_of(params, batch):
        return jax.value_and_grad(loss_fn, has_aux=True)(params, batch)

    def train_step(state: TrainState, batch):
        with jax.named_scope("backward"):
            loss, aux, grads = loss_and_grads(state, batch)
        with jax.named_scope("update"):
            updates, opt_state = optimizer.update(grads, state.opt_state,
                                                  state.params)
            params = apply_updates(state.params, updates)
        touched = dict(state.touched)
        for name, mask in aux.get("touched", {}).items():
            if name in touched:
                touched[name] = jnp.logical_or(touched[name], mask)
        metrics = {k: v for k, v in aux.items() if k != "touched"}
        metrics["loss"] = loss
        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state, touched=touched,
                               rng=state.rng)
        return new_state, metrics

    def loss_and_grads(state: TrainState, batch):
        if n_micro == 1:
            (loss, aux), grads = grads_of(state.params, batch)
        else:
            micro = jax.tree.map(
                lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
                batch)

            def body(acc, mb):
                g_acc, l_acc, t_acc = acc
                (loss, aux), g = grads_of(state.params, mb)
                g_acc = jax.tree.map(jnp.add, g_acc, g)
                t_new = {k: jnp.logical_or(t_acc[k], v) if k in t_acc else v
                         for k, v in aux.get("touched", {}).items()}
                return (g_acc, l_acc + loss,
                        {**t_acc, **t_new}), {k: v for k, v in aux.items()
                                              if k != "touched"}

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state.params)
            t0 = {k: jnp.zeros_like(v) for k, v in state.touched.items()}
            (grads, loss_sum, touched_acc), aux_stack = jax.lax.scan(
                body, (g0, jnp.zeros((), jnp.float32), t0), micro)
            grads = jax.tree.map(lambda g: g / n_micro, grads)
            loss = loss_sum / n_micro
            aux = {k: jnp.mean(v, axis=0) for k, v in aux_stack.items()}
            aux["touched"] = touched_acc
        return loss, aux, grads

    return train_step
