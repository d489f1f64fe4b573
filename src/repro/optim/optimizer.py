"""Optimizers + LR schedules (self-contained; optax is not available).

AdamW for the small/medium archs; Adafactor (factored second moments — the
PaLM/T5 TPU-production choice) for the 72B/314B configs where Adam's fp32
state would not fit a single pod (DESIGN.md §5).  Schedules include minicpm's
WSD (warmup-stable-decay).

Optimizer state mirrors parameter sharding: state_axes() maps each state
leaf to logical axes derived from the param schema so dist.sharding can
shard m/v/factored stats exactly like the weights.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, is_spec


# ---------------------------------------------------------------------------
# LR schedules
# ---------------------------------------------------------------------------


def wsd_schedule(peak_lr: float, warmup: int, stable: int, decay: int,
                 floor_frac: float = 0.1) -> Callable:
    """MiniCPM's warmup-stable-decay [arXiv:2404.06395]."""

    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = peak_lr * jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        in_decay = jnp.clip((step - warmup - stable) / jnp.maximum(decay, 1), 0.0, 1.0)
        dec = peak_lr * (1.0 - (1.0 - floor_frac) * in_decay)
        return jnp.where(step < warmup + stable, warm, dec)

    return lr


def cosine_schedule(peak_lr: float, warmup: int, total: int,
                    floor_frac: float = 0.1) -> Callable:
    def lr(step):
        step = jnp.asarray(step, jnp.float32)
        warm = peak_lr * jnp.minimum(step / jnp.maximum(warmup, 1), 1.0)
        t = jnp.clip((step - warmup) / jnp.maximum(total - warmup, 1), 0.0, 1.0)
        cos = peak_lr * (floor_frac + (1 - floor_frac) * 0.5 * (1 + jnp.cos(math.pi * t)))
        return jnp.where(step < warmup, warm, cos)

    return lr


def constant_schedule(lr_val: float) -> Callable:
    return lambda step: jnp.asarray(lr_val, jnp.float32)


# ---------------------------------------------------------------------------
# Optimizer interface
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class Optimizer:
    init: Callable  # params -> opt_state
    update: Callable  # (grads, opt_state, params) -> (new_params, new_opt_state)
    state_schema: Callable  # param schema -> opt-state schema (ParamSpec tree)


def global_norm(tree) -> jax.Array:
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32))) for x in jax.tree.leaves(tree)]
    return jnp.sqrt(sum(leaves))


def clip_by_global_norm(grads, max_norm: float):
    norm = global_norm(grads)
    scale = jnp.minimum(1.0, max_norm / (norm + 1e-9))
    return jax.tree.map(lambda g: (g * scale).astype(g.dtype), grads), norm


# ---------------------------------------------------------------------------
# AdamW
# ---------------------------------------------------------------------------


def adamw(
    lr: Callable,
    b1: float = 0.9,
    b2: float = 0.95,
    eps: float = 1.0e-8,
    weight_decay: float = 0.1,
    max_grad_norm: float = 1.0,
) -> Optimizer:
    def init(params):
        zeros = lambda p: jnp.zeros_like(p, dtype=jnp.float32)
        return {
            "m": jax.tree.map(zeros, params),
            "v": jax.tree.map(zeros, params),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        lr_t = lr(cf)

        def upd(g, m, v, p):
            g = g.astype(jnp.float32)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * jnp.square(g)
            mhat = m / (1 - b1 ** cf)
            vhat = v / (1 - b2 ** cf)
            step = mhat / (jnp.sqrt(vhat) + eps)
            decay = weight_decay * p.astype(jnp.float32) * (p.ndim >= 2)
            new_p = p.astype(jnp.float32) - lr_t * (step + decay)
            return new_p.astype(p.dtype), m, v

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_m = tdef.flatten_up_to(state["m"])
        flat_v = tdef.flatten_up_to(state["v"])
        res = [upd(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
        new_p = tdef.unflatten([r[0] for r in res])
        new_m = tdef.unflatten([r[1] for r in res])
        new_v = tdef.unflatten([r[2] for r in res])
        return new_p, {"m": new_m, "v": new_v, "count": count}

    def state_schema(schema):
        moment = lambda s: ParamSpec(s.shape, s.axes, init="zeros", dtype="float32")
        return {
            "m": jax.tree.map(moment, schema, is_leaf=is_spec),
            "v": jax.tree.map(moment, schema, is_leaf=is_spec),
            "count": ParamSpec((), (), init="zeros", dtype="int32"),
        }

    return Optimizer(init, update, state_schema)


# ---------------------------------------------------------------------------
# Adafactor (factored second moments over the trailing two dims)
# ---------------------------------------------------------------------------


def _factored(p) -> bool:
    return p.ndim >= 2 and p.shape[-1] >= 2 and p.shape[-2] >= 2


def adafactor(
    lr: Callable,
    decay: float = 0.8,
    eps: float = 1.0e-30,
    clip_threshold: float = 1.0,
    max_grad_norm: float = 1.0,
) -> Optimizer:
    def init(params):
        def st(p):
            if _factored(p):
                return {
                    "vr": jnp.zeros(p.shape[:-1], jnp.float32),
                    "vc": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32),
                }
            return {"v": jnp.zeros_like(p, dtype=jnp.float32)}

        return {
            "stats": jax.tree.map(st, params, is_leaf=lambda x: hasattr(x, "ndim")),
            "count": jnp.zeros((), jnp.int32),
        }

    def update(grads, state, params):
        grads, gnorm = clip_by_global_norm(grads, max_grad_norm)
        count = state["count"] + 1
        cf = count.astype(jnp.float32)
        beta = 1.0 - cf ** (-decay)
        lr_t = lr(cf)

        def upd(g, st, p):
            g = g.astype(jnp.float32)
            g2 = jnp.square(g) + eps
            if _factored(p):
                vr = beta * st["vr"] + (1 - beta) * jnp.mean(g2, axis=-1)
                vc = beta * st["vc"] + (1 - beta) * jnp.mean(g2, axis=-2)
                rfac = vr / jnp.maximum(jnp.mean(vr, axis=-1, keepdims=True), eps)
                u = g / (jnp.sqrt(rfac)[..., None] * jnp.sqrt(vc)[..., None, :] + eps)
                new_st = {"vr": vr, "vc": vc}
            else:
                v = beta * st["v"] + (1 - beta) * g2
                u = g / (jnp.sqrt(v) + eps)
                new_st = {"v": v}
            # update clipping (RMS <= clip_threshold)
            rms = jnp.sqrt(jnp.mean(jnp.square(u)) + eps)
            u = u / jnp.maximum(1.0, rms / clip_threshold)
            new_p = p.astype(jnp.float32) - lr_t * u
            return new_p.astype(p.dtype), new_st

        flat_p, tdef = jax.tree.flatten(params)
        flat_g = tdef.flatten_up_to(grads)
        flat_s = tdef.flatten_up_to(state["stats"])
        res = [upd(g, s, p) for g, s, p in zip(flat_g, flat_s, flat_p)]
        new_p = tdef.unflatten([r[0] for r in res])
        new_s = tdef.unflatten([r[1] for r in res])
        return new_p, {"stats": new_s, "count": count}

    def state_schema(schema):
        def st(s):
            if len(s.shape) >= 2 and s.shape[-1] >= 2 and s.shape[-2] >= 2:
                return {
                    "vr": ParamSpec(s.shape[:-1], s.axes[:-1], init="zeros", dtype="float32"),
                    "vc": ParamSpec(s.shape[:-2] + s.shape[-1:], s.axes[:-2] + s.axes[-1:],
                                    init="zeros", dtype="float32"),
                }
            return {"v": ParamSpec(s.shape, s.axes, init="zeros", dtype="float32")}

        return {
            "stats": jax.tree.map(st, schema, is_leaf=is_spec),
            "count": ParamSpec((), (), init="zeros", dtype="int32"),
        }

    return Optimizer(init, update, state_schema)


def make_optimizer(cfg, total_steps: int = 10_000,
                   peak_lr: Optional[float] = None) -> Optimizer:
    """The arch's optimizer and schedule; ``peak_lr`` overrides the
    schedule's peak (3e-4, or minicpm's 3e-4 WSD peak)."""
    peak = 3e-4 if peak_lr is None else peak_lr
    if cfg.name.startswith("minicpm"):
        sched = wsd_schedule(peak, warmup=int(0.01 * total_steps),
                             stable=int(0.79 * total_steps), decay=int(0.2 * total_steps))
    else:
        sched = cosine_schedule(peak, warmup=min(2000, total_steps // 10), total=total_steps)
    if cfg.optimizer == "adafactor":
        return adafactor(sched)
    return adamw(sched)
