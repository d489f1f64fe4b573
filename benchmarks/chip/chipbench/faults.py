"""Faults planted in the program's training step.

The comparison that decides ``correct`` has to fail each fault that a cell
can have.  The tests plant them at a reduced size on the CPU, and
``calibrate.py --faults`` at the cell's own size on its chips, where a
fault's readings bound a limit from above.  Each is planted by replacing
``repro.train.step.make_train_step``, which ``jit_train_step`` calls, for
the models of one depth (the foreground's or a tenant's), on a mesh of
``data`` replicas of ``model`` chips each:

- ``state_unchanged``: the step returns the state it was given;
- ``update_doubled``: the weights move twice as far as the step says;
- ``half_batch``: the step sees the first half of the batch's rows, and its
  loss is the mean over them (a cell with two rows or more);
- ``exchange_left_out``: no gradient is exchanged between the data
  replicas: each block of a weight's last dimension, one block a replica
  as an FSDP chip holds it, takes the gradient of that replica's rows
  alone (a leaf the replicas do not divide takes their mean; a cell with
  ``data`` of 2 or more);
- ``model_exchange_left_out``: the partial sums of the tensor-parallel MLP
  are not added up over the model axis: its down projection sums over the
  first of ``model`` blocks of its hidden units alone, as the first chip
  of a replica holds them (a cell with ``model`` of 2 or more; the dense
  decoder's MLP is split over the model axis wherever its width divides).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

FAULTS = ("state_unchanged", "update_doubled", "half_batch",
          "exchange_left_out", "model_exchange_left_out")


def _rows(batch, lo, n):
    return {k: jax.lax.dynamic_slice_in_dim(v, lo, n, 0)
            for k, v in batch.items()}


def _local_grads(api, params, batch, replicas: int):
    """Each replica's gradient, over its own rows, on its own block of
    every leaf's last dim."""
    B = batch["tokens"].shape[0]
    rows = B // replicas

    def own(g, i):
        n = g.shape[-1] if g.ndim else 0
        if not n or n % replicas:
            return g / replicas
        return jnp.where(jnp.arange(n) // (n // replicas) == i, g, 0.0)

    def body(acc, i):
        g = jax.grad(lambda p: api.loss(p, _rows(batch, i * rows, rows))[0])(
            params)
        return jax.tree.map(lambda a, gi: a + own(gi, i), acc, g), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    return jax.lax.scan(body, zeros, jnp.arange(replicas))[0]


@contextlib.contextmanager
def _first_model_shard(model: int):
    """The model's MLP, for the length of the block, with its down
    projection summed over the first of ``model`` blocks of its hidden
    units alone."""
    from repro.models import layers, transformer

    def mlp_apply(p, x):
        f = p["wd"].shape[0] // model
        return layers.mlp_apply({"wg": p["wg"][:, :f], "wu": p["wu"][:, :f],
                                 "wd": p["wd"][:f]}, x)

    sound, transformer.mlp_apply = transformer.mlp_apply, mlp_apply
    try:
        yield
    finally:
        transformer.mlp_apply = sound


def faulty(make_train_step, fault: str, layers: int, data: int = 1,
           model: int = 1):
    """``make_train_step`` with ``fault`` planted in the steps of models of
    ``layers`` layers, on a mesh of ``data`` replicas of ``model`` chips."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")
    if fault == "exchange_left_out" and data < 2:
        raise ValueError(f"{fault} needs 2 or more data replicas")
    if fault == "model_exchange_left_out" and model < 2:
        raise ValueError(f"{fault} needs a model axis of 2 or more chips")

    def make(api, optimizer, grad_transform=None):
        sound = make_train_step(api, optimizer, grad_transform)
        if api.cfg.num_layers != layers:
            return sound

        def step(state, batch):
            if fault == "half_batch":
                return sound(state, _rows(batch, 0,
                                          batch["tokens"].shape[0] // 2))
            if fault == "exchange_left_out":
                local = _local_grads(api, state["params"], batch, data)
                return make_train_step(api, optimizer,
                                       lambda _: local)(state, batch)
            if fault == "model_exchange_left_out":
                with _first_model_shard(model):  # the step traces here
                    return sound(state, batch)
            new_state, metrics = sound(state, batch)
            if fault == "state_unchanged":
                return state, metrics
            params = jax.tree.map(lambda a, b: 2 * a - b,
                                  new_state["params"], state["params"])
            return dict(new_state, params=params), metrics

        return step

    return make


@contextlib.contextmanager
def planted(fault: str, layers: int, data: int = 1, model: int = 1):
    """``fault`` planted in the program for the length of the block."""
    from repro.train import step

    sound = step.make_train_step
    step.make_train_step = faulty(sound, fault, layers, data, model)
    try:
        yield
    finally:
        step.make_train_step = sound
