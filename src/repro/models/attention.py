"""Attention execution paths.

Four paths; ``attend`` picks one from what it can observe (backend, shape,
soft-cap):
  - flash (``_flash``)     : the Pallas kernel (kernels/flash_attention.py),
                  forward and backward, for self-attention on a TPU at long
                  sequences; under a multi-device ``dist.fsdp`` context it
                  runs inside ``shard_map`` (batch over the data axes, heads
                  over the model axis).
  - ``blocked_attention`` : lax.scan over query blocks; memory bounded by
                  BLOCK_Q × Skv.  Long sequences everywhere else (CPU,
                  cross-attention, soft-capped logits).
  - ``full_attention``    : materializes (Sq, Skv) scores.  Short sequences.
  - ``decode_attention``  : single-query attention against a KV cache.

All paths implement GQA natively (no KV head repetition) plus causal and
sliding-window masking; the XLA paths also grok-style logit soft-capping.
``count_paths`` counts, while a program traces, which path each ``attend``
call took.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import fsdp
from repro.kernels import flash_attention as _fa
from repro.models.layers import PathCounter

NEG_INF = -1.0e30
MIN_BLOCKED_LEN = 2048  # shorter sequences take full_attention
BLOCK_Q = 512  # query rows per step of ``blocked_attention``'s scan


def _softcap(scores: jax.Array, cap: float) -> jax.Array:
    if cap and cap > 0.0:
        return cap * jnp.tanh(scores / cap)
    return scores


def _mask_bias(
    q_pos: jax.Array,  # (Sq,) absolute positions of queries
    k_pos: jax.Array,  # (Skv,) absolute positions of keys
    causal: bool,
    window: int,
) -> jax.Array:
    """Additive mask (Sq, Skv) in fp32."""
    ok = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        ok &= k_pos[None, :] <= q_pos[:, None]
    if window and window > 0:
        ok &= k_pos[None, :] > (q_pos[:, None] - window)
    return jnp.where(ok, 0.0, NEG_INF)


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,KV,G,hd), k: (B,Skv,KV,hd) -> (B,KV,G,Sq,Skv) fp32."""
    return jnp.einsum("bsngh,btnh->bngst", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (B,KV,G,Sq,Skv) fp32, v: (B,Skv,KV,hd) -> (B,Sq,KV,G,hd)."""
    return jnp.einsum("bngst,btnh->bsngh", p, v.astype(p.dtype))


def full_attention(
    q: jax.Array,  # (B, Sq, H, hd)
    k: jax.Array,  # (B, Skv, KV, hd)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
    q_offset: int = 0,
) -> jax.Array:
    B, Sq, H, hd = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, hd) * (1.0 / math.sqrt(hd))
    scores = _gqa_scores(qg, k)
    scores = _softcap(scores, softcap)
    q_pos = jnp.arange(Sq) + q_offset
    k_pos = jnp.arange(k.shape[1])
    scores = scores + _mask_bias(q_pos, k_pos, causal, window)
    p = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(p, v)
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def blocked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Scan over query blocks of ``BLOCK_Q`` rows, which must divide Sq;
    each block softmaxes over its full (masked) key row, so no
    online-softmax state is needed and peak memory is O(BLOCK_Q × Skv) per
    head group."""
    B, Sq, H, hd = q.shape
    block_q = BLOCK_Q
    KV = k.shape[2]
    G = H // KV
    nblk = Sq // block_q
    qg = q.reshape(B, nblk, block_q, KV, G, hd) * (1.0 / math.sqrt(hd))
    qg = jnp.moveaxis(qg, 1, 0)  # (nblk, B, block_q, KV, G, hd)
    k_pos = jnp.arange(k.shape[1])

    def body(carry, inp):
        blk_idx, qb = inp
        scores = _gqa_scores(qb, k)
        scores = _softcap(scores, softcap)
        q_pos = blk_idx * block_q + jnp.arange(block_q)
        ok = jnp.ones((block_q, k.shape[1]), dtype=bool)
        if causal:
            ok &= k_pos[None, :] <= q_pos[:, None]
        if window and window > 0:
            ok &= k_pos[None, :] > (q_pos[:, None] - window)
        scores = scores + jnp.where(ok, 0.0, NEG_INF)
        p = jax.nn.softmax(scores, axis=-1)
        ob = _gqa_out(p, v).astype(q.dtype)  # (B, block_q, KV, G, hd)
        return carry, ob

    _, out = jax.lax.scan(body, None, (jnp.arange(nblk), qg))
    out = jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, hd)
    return out


def decode_attention(
    q: jax.Array,  # (B, 1, H, hd)
    k_cache: jax.Array,  # (B, Skv, KV, hd)
    v_cache: jax.Array,
    cache_len: jax.Array,  # (B,) or scalar — number of valid cache entries
    *,
    window: int = 0,
    softcap: float = 0.0,
) -> jax.Array:
    """Single new query attends over the valid prefix of the cache."""
    B, _, H, hd = q.shape
    Skv, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q.reshape(B, 1, KV, G, hd) * (1.0 / math.sqrt(hd))
    scores = _gqa_scores(qg, k_cache)  # (B,KV,G,1,Skv)
    scores = _softcap(scores, softcap)
    k_pos = jnp.arange(Skv)
    valid = k_pos[None, :] < jnp.reshape(cache_len, (-1, 1))  # (B, Skv)
    if window and window > 0:
        valid &= k_pos[None, :] >= (jnp.reshape(cache_len, (-1, 1)) - window)
    bias = jnp.where(valid, 0.0, NEG_INF)[:, None, None, None, :]
    p = jax.nn.softmax(scores + bias, axis=-1)
    out = _gqa_out(p, v_cache)
    return out.reshape(B, 1, H, hd).astype(q.dtype)


# the paths (``flash``, ``blocked``, ``full``) that ``attend`` takes
count_paths = PathCounter()


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _flash(q, k, v, causal, window):
    """The Pallas kernel; under a multi-device fsdp context inside
    ``shard_map``, since GSPMD cannot partition a custom call."""
    fn = partial(_fa.flash_attention, causal=causal, window=window)
    ctx = fsdp.active()
    if ctx is None or ctx[0].devices.size == 1:
        return fn(q, k, v)
    from repro.dist.sharding import pspec  # imports models: not at the top

    mesh, rules = ctx
    q_spec = pspec(("act_batch", None, "heads", None), q.shape, rules, mesh)
    kv_spec = pspec(("act_batch", None, "kv_heads", None), k.shape, rules, mesh)
    if tuple(q_spec)[2:3] != tuple(kv_spec)[2:3]:
        # a query head must find its kv head on its own shard
        q_spec = kv_spec = P(*tuple(q_spec)[:1])
    return jax.shard_map(fn, mesh=mesh, in_specs=(q_spec, kv_spec, kv_spec),
                         out_specs=q_spec, check_vma=False)(q, k, v)


def attend(q, k, v, *, causal=True, window=0, softcap=0.0):
    """Shape-dispatching attention used by the model forward passes: from
    ``MIN_BLOCKED_LEN`` positions on, the Pallas kernel for self-attention
    on a TPU without a soft-cap, else ``blocked_attention``; below it
    ``full_attention``."""
    Sq, Skv = q.shape[1], k.shape[1]
    if Sq < MIN_BLOCKED_LEN:
        path = "full"
    elif (_on_tpu() and Sq == Skv and _fa.block_size(Sq) and not softcap):
        path = "flash"
    elif Sq % BLOCK_Q == 0:
        path = "blocked"
    else:
        path = "full"
    count_paths.add(path)
    if path == "flash":
        return _flash(q, k, v, causal, window)
    if path == "blocked":
        return blocked_attention(q, k, v, causal=causal, window=window,
                                 softcap=softcap)
    return full_attention(q, k, v, causal=causal, window=window, softcap=softcap)
