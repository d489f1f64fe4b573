"""Faults planted in the program's training step.

The comparison that decides ``correct`` has to fail each fault that a cell
can have.  The tests plant them at a reduced size on the CPU, and
``calibrate.py --fault`` at the cell's own size on its chips, where a
fault's readings bound a limit from above.  Each is planted by replacing
``repro.train.step.make_train_step``, which ``jit_train_step`` calls, for
the models of one depth (the foreground's or a tenant's):

- ``state_unchanged``: the step returns the state it was given;
- ``update_doubled``: the weights move twice as far as the step says;
- ``half_batch``: the step sees the first half of the batch's rows, and its
  loss is the mean over them (a cell with two rows or more);
- ``exchange_left_out``: no gradient is exchanged between the chips: each
  block of a weight's last dimension, one block a row as an FSDP chip of a
  one-row-a-chip batch holds it, takes the gradient of that row alone (a
  leaf the rows do not divide takes their mean).
"""
from __future__ import annotations

import contextlib

import jax
import jax.numpy as jnp

FAULTS = ("state_unchanged", "update_doubled", "half_batch",
          "exchange_left_out")


def _rows(batch, lo, n):
    return {k: jax.lax.dynamic_slice_in_dim(v, lo, n, 0)
            for k, v in batch.items()}


def _local_grads(api, params, batch):
    """Each row's gradient on its own block of every leaf's last dim."""
    B = batch["tokens"].shape[0]

    def own(g, i):
        n = g.shape[-1] if g.ndim else 0
        if not n or n % B:
            return g / B
        return jnp.where(jnp.arange(n) // (n // B) == i, g, 0.0)

    def body(acc, i):
        g = jax.grad(lambda p: api.loss(p, _rows(batch, i, 1))[0])(params)
        return jax.tree.map(lambda a, gi: a + own(gi, i), acc, g), None

    zeros = jax.tree.map(jnp.zeros_like, params)
    return jax.lax.scan(body, zeros, jnp.arange(B))[0]


def faulty(make_train_step, fault: str, layers: int):
    """``make_train_step`` with ``fault`` planted in the steps of models of
    ``layers`` layers."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}; known: {FAULTS}")

    def make(api, optimizer, grad_transform=None):
        sound = make_train_step(api, optimizer, grad_transform)
        if api.cfg.num_layers != layers:
            return sound

        def step(state, batch):
            if fault == "half_batch":
                return sound(state, _rows(batch, 0,
                                          batch["tokens"].shape[0] // 2))
            if fault == "exchange_left_out":
                local = _local_grads(api, state["params"], batch)
                return make_train_step(api, optimizer,
                                       lambda _: local)(state, batch)
            new_state, metrics = sound(state, batch)
            if fault == "state_unchanged":
                return state, metrics
            params = jax.tree.map(lambda a, b: 2 * a - b,
                                  new_state["params"], state["params"])
            return dict(new_state, params=params), metrics

        return step

    return make


@contextlib.contextmanager
def planted(fault: str, layers: int):
    """``fault`` planted in the program for the length of the block."""
    from repro.train import step

    sound = step.make_train_step
    step.make_train_step = faulty(sound, fault, layers)
    try:
        yield
    finally:
        step.make_train_step = sound
