"""What every model's float32 reference shares: the training step around
its loss, and the readings taken from it.

A configuration names its reference (``"reference"`` in its file), a
module under ``references/`` that ``spec.reference`` finds by name.  The
module gives the model: ``param_shapes(c)``, the program's parameter
layout, from which the seeded weights and the leaf paths come;
``loss(params, batch, c, prec)``, the plain forward pass and mean
next-token loss; and ``train_flops_per_token(c, seq)``.  This module gives
the rest, written from the configuration's ``train`` recipe and importing
nothing of the program: the learning-rate schedule, global-norm clipping,
AdamW, and three steps from the seeded weights.  Matmuls run at
``Precision.HIGHEST`` through ``mm``.

``prec="fp8"`` is the control: every matmul operand rounded to float8
(e4m3, one scale per tensor), one step below the configurations' bfloat16.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench import inputs

HI = jax.lax.Precision.HIGHEST


def _round(x, prec: str):
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0 + 1e-30)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight through


def mm(eq, a, b, prec):
    """``einsum`` at ``Precision.HIGHEST``, its operands rounded to
    ``prec``."""
    return jnp.einsum(eq, _round(a, prec), _round(b, prec), precision=HI)


def learning_rate(t: dict, total: int, count):
    """The step size at optimizer step ``count`` (1 for the first update)
    of a ``total``-step schedule, as the configuration's ``train`` states."""
    peak, floor = t["peak_lr"], t["floor_frac"]
    count = jnp.asarray(count, jnp.float32)
    if t["schedule"] == "cosine":
        warm = min(2000, total // 10)
        ramp = peak * jnp.minimum(count / max(warm, 1), 1.0)
        frac = jnp.clip((count - warm) / max(total - warm, 1), 0.0, 1.0)
        cos = peak * (floor + (1 - floor) * 0.5
                      * (1 + jnp.cos(math.pi * frac)))
        return jnp.where(count < warm, ramp, cos)
    if t["schedule"] == "wsd":
        warm, stable, decay = (int(0.01 * total), int(0.79 * total),
                               int(0.2 * total))
        ramp = peak * jnp.minimum(count / max(warm, 1), 1.0)
        frac = jnp.clip((count - warm - stable) / max(decay, 1), 0.0, 1.0)
        return jnp.where(count < warm + stable, ramp,
                         peak * (1.0 - (1.0 - floor) * frac))
    raise ValueError(f"unknown schedule {t['schedule']!r}")


def leaf_norms(tree) -> jax.Array:
    """Per-leaf L2 norms, in flattening order."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def make_step(model, c: dict, t: dict, total: int, prec: str = "f32"):
    """``step(params, m, v, count, batch) -> (loss, clipped-gradient leaf
    norms, params, m, v)``: one AdamW step on ``model.loss`` (``model`` is
    the configuration's reference module), params and moments donated."""
    b1, b2, eps, wd = t["b1"], t["b2"], t["eps"], t["weight_decay"]

    def step(params, m, v, count, batch):
        value, grads = jax.value_and_grad(model.loss)(params, batch, c, prec)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, t["max_grad_norm"] / (gnorm + 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        n = count + 1.0
        lr = learning_rate(t, total, n)

        def upd(g, mi, vi, p):
            mi = b1 * mi + (1 - b1) * g
            vi = b2 * vi + (1 - b2) * g * g
            u = (mi / (1 - b1 ** n)) / (jnp.sqrt(vi / (1 - b2 ** n)) + eps)
            decay = wd * p if p.ndim >= t["decay_min_ndim"] else 0.0
            return p - lr * (u + decay), mi, vi

        out = jax.tree.map(upd, grads, m, v, params)
        pick = lambda i: jax.tree.map(  # noqa: E731
            lambda o: o[i], out, is_leaf=lambda o: isinstance(o, tuple))
        return value, leaf_norms(grads), pick(0), pick(1), pick(2)

    return jax.jit(step, donate_argnums=(0, 1, 2))


def delta_norms_fn(role: int, shapes):
    """``fn(params, words) ->`` per-leaf norms of ``params`` minus the
    seeded initial weights of ``role`` (regenerated, never kept).  The seed
    is an argument, so one compiled program serves every seed."""
    return jax.jit(lambda p, words: leaf_norms(jax.tree.map(
        jnp.subtract, p, inputs.make_params(words, role, shapes))))


@jax.jit
def _diff_norm(a, b):
    return jnp.sqrt(jnp.sum(jnp.square(a - jnp.asarray(b, a.dtype))))


def run(model, c: dict, t: dict, total: int, words, role: int, batches,
        shardings=None, prec: str = "f32", steps: int = 3,
        other_m=None, keep_m: bool = False) -> dict:
    """Train ``model`` (the configuration's reference module) ``steps``
    steps from the seeded weights on ``batches(k)``.
    Returns the first step's clipped-gradient leaf norms and the leaf norms
    of the change after ``steps`` updates.  With
    ``other_m`` (Adam's first moment after one step of another run, on the
    host), also the leaf norms of the difference of the two first
    gradients; with ``keep_m``, this run's own first moment, on the host."""
    shapes = model.param_shapes(c)
    init = jax.jit(lambda w: inputs.make_params(w, role, shapes),
                   out_shardings=shardings)
    params = init(words)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    step = make_step(model, c, t, total, prec)
    paths = inputs.leaf_paths(shapes)
    out = {}
    for k in range(steps):
        _, gn, params, m, v = step(params, m, v, jnp.float32(k), batches(k))
        if k == 0:
            out["grad_norms"] = dict(zip(paths, map(float, jax.device_get(
                gn))))
            scale = 1.0 / (1.0 - t["b1"])  # m = (1 - b1) g after one step
            if other_m is not None:
                out["grad_diff_norms"] = {
                    p: scale * float(_diff_norm(a, b)) for p, a, b in zip(
                        paths, jax.tree.leaves(m), jax.tree.leaves(other_m))}
            if keep_m:
                out["m0"] = jax.device_get(m)
    del m, v
    delta = jax.device_get(delta_norms_fn(role, shapes)(params, words))
    out["delta_norms"] = dict(zip(paths, map(float, delta)))
    return out
