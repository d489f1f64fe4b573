"""Core layers + parameter-schema machinery (pure functional, no flax).

A model is described by a *schema*: a pytree of ``ParamSpec`` leaves.  From
one schema we derive
  - ``init_params``      : random arrays (jit-friendly),
  - ``abstract_params``  : ShapeDtypeStructs (dry-run, no allocation),
  - ``logical_axes``     : pytree of logical-axis-name tuples, which
                           dist.sharding maps to mesh PartitionSpecs.

Logical axis vocabulary (mapping decided per-config in dist/sharding.py):
  'layers'    leading stacked-layer axis (scan dim)           -> never sharded
  'embed'     d_model dim of weights                          -> FSDP ('data')
  'heads'     query-head dim                                  -> TP ('model')
  'kv_heads'  kv-head dim                                     -> TP or replicated
  'head_dim'  per-head feature dim                            -> never sharded
  'mlp'       d_ff dim                                        -> TP ('model')
  'vocab'     vocabulary dim                                  -> TP ('model')
  'expert'    MoE expert dim                                  -> EP ('model') or None
  'moe_mlp'   per-expert d_ff dim                             -> TP for grok-style
  'ssm_inner' mamba inner dim                                 -> TP ('model')
  'ssm_state' SSM state dim                                   -> never sharded
  'norm'      norm scales / biases / small vectors            -> replicated
"""
from __future__ import annotations

import collections
import dataclasses
import math
from contextlib import contextmanager
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Param schema
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ParamSpec:
    shape: tuple
    axes: tuple  # logical axis names, len == len(shape)
    init: str = "normal"  # 'normal' | 'zeros' | 'ones' | 'embed' | 'small_normal'
    dtype: str = "float32"
    scale: Optional[float] = None  # override init std

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def _fan_in(shape: tuple) -> int:
    if len(shape) == 1:
        return shape[0]
    # weights laid out (in..., out...) — use product of all but last dim group;
    # we approximate fan_in as prod(shape[:-1]) capped for 3d head layouts.
    return int(max(1, math.prod(shape[:-1])))


def init_leaf(key: jax.Array, spec: ParamSpec) -> jax.Array:
    dt = jnp.dtype(spec.dtype)
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dt)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dt)
    if spec.init == "embed":
        std = spec.scale if spec.scale is not None else 0.02
        return (jax.random.normal(key, spec.shape) * std).astype(dt)
    std = spec.scale if spec.scale is not None else 1.0 / math.sqrt(_fan_in(spec.shape))
    if spec.init == "small_normal":
        std = spec.scale if spec.scale is not None else 0.02
    return (jax.random.normal(key, spec.shape) * std).astype(dt)


def is_spec(x: Any) -> bool:
    return isinstance(x, ParamSpec)


def init_params(rng: jax.Array, schema: Any) -> Any:
    leaves, treedef = jax.tree.flatten(schema, is_leaf=is_spec)
    keys = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(treedef, [init_leaf(k, s) for k, s in zip(keys, leaves)])


def abstract_params(schema: Any) -> Any:
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, jnp.dtype(s.dtype)), schema, is_leaf=is_spec
    )


def logical_axes(schema: Any) -> Any:
    return jax.tree.map(lambda s: s.axes, schema, is_leaf=is_spec)


def stack_schema(schema: Any, n: int) -> Any:
    """Prepend a stacked 'layers' axis to every spec (scan-over-layers)."""
    return jax.tree.map(
        lambda s: ParamSpec((n,) + s.shape, ("layers",) + s.axes, s.init, s.dtype, s.scale),
        schema,
        is_leaf=is_spec,
    )


def param_count(schema: Any) -> int:
    leaves, _ = jax.tree.flatten(schema, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


class PathCounter:
    """``with counter() as counts``: a Counter of the paths a layer reports
    (``counter.add(path)``) while a program traces inside the block.  Counts
    are taken at trace time only, so a jit cache hit counts nothing, and one
    traced call under ``lax.scan`` stands for every iteration.  Blocks nest:
    each open one counts."""

    def __init__(self):
        self._open: list = []  # innermost last

    @contextmanager
    def __call__(self):
        counts = collections.Counter()
        self._open.append(counts)
        try:
            yield counts
        finally:
            self._open.pop()

    def add(self, path: str) -> None:
        for counts in self._open:
            counts[path] += 1


# ---------------------------------------------------------------------------
# Numerics helpers
# ---------------------------------------------------------------------------


def cast(x: jax.Array, dtype: str) -> jax.Array:
    return x.astype(jnp.dtype(dtype))


def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up


# ---------------------------------------------------------------------------
# Rotary position embeddings
# ---------------------------------------------------------------------------


def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., S, n_heads, d_head); positions: (..., S) int32."""
    d_head = x.shape[-1]
    freqs = rope_freqs(d_head, theta)  # (d_head/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, d/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, d/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Schema builders for common sub-modules
# ---------------------------------------------------------------------------


def attention_schema(cfg) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    s: dict = {
        "wq": ParamSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        s["bq"] = ParamSpec((H, hd), ("heads", "head_dim"), init="zeros")
        s["bk"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
        s["bv"] = ParamSpec((KV, hd), ("kv_heads", "head_dim"), init="zeros")
    return s


def mlp_schema(cfg) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    return {
        "wg": ParamSpec((D, F), ("embed", "mlp")),
        "wu": ParamSpec((D, F), ("embed", "mlp")),
        "wd": ParamSpec((F, D), ("mlp", "embed")),
    }


def qkv_project(p: dict, x: jax.Array, cfg) -> tuple:
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, cast(p["wq"], dt))
    k = jnp.einsum("bsd,dhk->bshk", x, cast(p["wk"], dt))
    v = jnp.einsum("bsd,dhk->bshk", x, cast(p["wv"], dt))
    if cfg.qkv_bias:
        q = q + cast(p["bq"], dt)
        k = k + cast(p["bk"], dt)
        v = v + cast(p["bv"], dt)
    return q, k, v


def out_project(p: dict, attn_out: jax.Array) -> jax.Array:
    return jnp.einsum("bshk,hkd->bsd", attn_out, cast(p["wo"], attn_out.dtype))


def mlp_apply(p: dict, x: jax.Array) -> jax.Array:
    dt = x.dtype
    g = jnp.einsum("bsd,df->bsf", x, cast(p["wg"], dt))
    u = jnp.einsum("bsd,df->bsf", x, cast(p["wu"], dt))
    return jnp.einsum("bsf,fd->bsd", swiglu(g, u), cast(p["wd"], dt))


# ---------------------------------------------------------------------------
# Loss
# ---------------------------------------------------------------------------


def softmax_xent(logits: jax.Array, labels: jax.Array, mask: Optional[jax.Array] = None):
    """logits: (B, S, V) any float dtype; labels int32 (B, S). fp32 reduction."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = logz - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
