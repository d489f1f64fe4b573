"""Decoder-only LM stack (dense + MoE variants) with scan-over-layers.

Covers: qwen2-72b, qwen2-1.5b, minicpm-2b, llama3-8b, pixtral-12b (backbone),
grok-1-314b, qwen3-moe-30b-a3b.  Layer stacks use ``jax.lax.scan`` over
stacked per-layer params with a configurable remat policy so the 80-layer
configs compile quickly and activation memory stays bounded.

Modes:
  forward(params, tokens)                        -> logits     (teacher forcing)
  prefill(params, tokens, cache_capacity)        -> (last-position logits, cache)
  decode_step(params, token, cache, cache_len)   -> (logits, cache)
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import (
    ParamSpec,
    apply_rope,
    attention_schema,
    cast,
    mlp_apply,
    mlp_schema,
    out_project,
    qkv_project,
    rms_norm,
    stack_schema,
)
from repro.models.moe import moe_apply, moe_schema
from repro.dist import fsdp

VISION_PREFIX = 1024  # pixtral: number of precomputed patch-embedding positions
LOSS_CHUNK = 512  # sequence positions per logits block in ``chunked_xent``


# ---------------------------------------------------------------------------
# Schema
# ---------------------------------------------------------------------------


def block_schema(cfg) -> dict:
    D = cfg.d_model
    s = {
        "ln1": ParamSpec((D,), ("norm",), init="zeros"),
        "ln2": ParamSpec((D,), ("norm",), init="zeros"),
        "attn": attention_schema(cfg),
    }
    if cfg.block_type == "moe":
        s["moe"] = moe_schema(cfg)
    else:
        s["mlp"] = mlp_schema(cfg)
    return s


def lm_schema(cfg) -> dict:
    D, Vp = cfg.d_model, cfg.padded_vocab
    schema = {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), init="embed"),
        "layers": stack_schema(block_schema(cfg), cfg.num_layers),
        "final_norm": ParamSpec((D,), ("norm",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        schema["lm_head"] = ParamSpec((D, Vp), ("embed", "vocab"))
    if cfg.frontend == "vision":
        schema["frontend_proj"] = ParamSpec((D, D), ("embed", "embed_out"))
    return schema


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def decoder_block(p: dict, h: jax.Array, positions: jax.Array, cfg) -> tuple:
    """Full-sequence (train/prefill) block. Returns (h, (k, v), aux).
    The two sub-layers run under the named scopes ``attention`` and
    ``mlp``, which the compiled ops carry in their ``op_name``."""
    with jax.named_scope("attention"):
        a_in = rms_norm(h, p["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(p["attn"], a_in, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        attn_out = attn_lib.attend(
            q, k, v, causal=True, window=cfg.sliding_window, softcap=cfg.attn_logit_softcap
        )
        h = h + out_project(p["attn"], attn_out)
    with jax.named_scope("mlp"):
        m_in = rms_norm(h, p["ln2"], cfg.norm_eps)
        if cfg.block_type == "moe":
            mlp_out, aux = moe_apply(p["moe"], m_in, cfg)
        else:
            mlp_out, aux = mlp_apply(p["mlp"], m_in), jnp.zeros((), jnp.float32)
        h = h + mlp_out
    return h, (k, v), aux


def decoder_block_decode(
    p: dict,
    h: jax.Array,  # (B, 1, D)
    k_cache: jax.Array,  # (B, cap, KV, hd)
    v_cache: jax.Array,
    cache_len: jax.Array,  # scalar int32, or (B,) per-row lengths
    cfg,
) -> tuple:
    B = h.shape[0]
    per_row = jnp.ndim(cache_len) == 1  # continuous batching: ragged lanes
    positions = jnp.broadcast_to(
        jnp.reshape(cache_len, (-1, 1)).astype(jnp.int32), (B, 1)
    )
    a_in = rms_norm(h, p["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(p["attn"], a_in, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    if per_row:
        # each lane appends at its own length (one scatter per row)
        rows = jnp.arange(B)
        k_cache = k_cache.at[rows, cache_len].set(k[:, 0].astype(k_cache.dtype))
        v_cache = v_cache.at[rows, cache_len].set(v[:, 0].astype(v_cache.dtype))
    else:
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), cache_len, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), cache_len, 1)
    attn_out = attn_lib.decode_attention(
        q,
        k_cache.astype(q.dtype),
        v_cache.astype(q.dtype),
        cache_len + 1,
        window=cfg.sliding_window,
        softcap=cfg.attn_logit_softcap,
    )
    h = h + out_project(p["attn"], attn_out)
    m_in = rms_norm(h, p["ln2"], cfg.norm_eps)
    if cfg.block_type == "moe":
        mlp_out, _ = moe_apply(p["moe"], m_in, cfg)
    else:
        mlp_out = mlp_apply(p["mlp"], m_in)
    return h + mlp_out, k_cache, v_cache


def _maybe_remat(fn, cfg):
    if cfg.remat_policy == "none":
        return fn
    if cfg.remat_policy == "dots":
        pol = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=pol)
    return jax.checkpoint(fn)  # 'full': save only layer boundaries


# ---------------------------------------------------------------------------
# Stacks
# ---------------------------------------------------------------------------


def embed_tokens(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    with jax.named_scope("embed"):
        emb = fsdp.gather_leaf(params["embed"], ("vocab", "embed"))
        return cast(emb, jnp.dtype(cfg.dtype))[tokens]


def _head(params: dict, cfg, dtype) -> tuple:
    """(einsum equation, gathered output-projection weight) of the LM head."""
    if cfg.tie_embeddings:
        w = fsdp.gather_leaf(params["embed"], ("vocab", "embed"))
        return "bsd,vd->bsv", cast(w, dtype)
    w = fsdp.gather_leaf(params["lm_head"], ("embed", "vocab"))
    return "bsd,dv->bsv", cast(w, dtype)


def unembed(params: dict, h: jax.Array, cfg) -> jax.Array:
    h = rms_norm(h, params["final_norm"], cfg.norm_eps)
    eq, w = _head(params, cfg, h.dtype)
    return jnp.einsum(eq, h, w)


def chunked_xent(params: dict, h: jax.Array, labels: jax.Array, cfg) -> jax.Array:
    """Masked mean cross-entropy of ``unembed(params, h)`` against ``labels``
    (-1 = masked): the LM loss of every decoder family.  It scans blocks of
    at most ``LOSS_CHUNK`` positions under ``jax.checkpoint``, so only one
    block's fp32 logits is live in the forward or the backward pass; a
    sequence that is not a whole number of blocks is padded with masked
    positions.  Whole-sequence logits and their gradient at a 152k
    vocabulary and 4096 positions take ~7 GB, half of a 16 GB chip.  The
    price is the head's forward matmul again in the backward pass.  It
    runs under the named scope ``loss_head``."""
    with jax.named_scope("loss_head"):
        B, S, _ = h.shape
        chunk = min(S, LOSS_CHUNK)
        pad = -S % chunk
        if pad:
            h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
            labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
            S += pad
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        eq, w = _head(params, cfg, h.dtype)

        @jax.checkpoint
        def block(carry, xs):
            hb, lb = xs
            logits = jnp.einsum(eq, hb, w).astype(jnp.float32)
            gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None], axis=-1)
            nll = jax.nn.logsumexp(logits, axis=-1) - gold[..., 0]
            valid = lb >= 0
            return (carry[0] + jnp.sum(jnp.where(valid, nll, 0.0)),
                    carry[1] + jnp.sum(valid.astype(jnp.float32))), None

        blocks = (jnp.swapaxes(h.reshape(B, S // chunk, chunk, -1), 0, 1),
                  jnp.swapaxes(labels.reshape(B, S // chunk, chunk), 0, 1))
        zero = jnp.zeros((), jnp.float32)
        (total, count), _ = jax.lax.scan(block, (zero, zero), blocks)
        return total / jnp.maximum(count, 1.0)


def hidden_states(params: dict, tokens: jax.Array, cfg, patch_embeds=None):
    """Embed (+ optional vision prefix) and run the layer stack."""
    h = embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and patch_embeds is not None:
        fp = fsdp.gather_leaf(params["frontend_proj"], ("embed", "embed_out"))
        pe = jnp.einsum("bsd,de->bse", patch_embeds.astype(h.dtype), cast(fp, h.dtype))
        h = jnp.concatenate([pe, h], axis=1)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    bschema = block_schema(cfg)
    blk = _maybe_remat(
        lambda lp, hh: decoder_block(fsdp.gather(lp, bschema), hh, positions, cfg), cfg
    )

    def body(carry, lp):
        hh, aux = carry
        hh, _, a = blk(lp, hh)
        return (hh, aux + a), None

    (h, aux), _ = jax.lax.scan(body, (h, jnp.zeros((), jnp.float32)), params["layers"])
    return h, aux / cfg.num_layers


def forward(params: dict, tokens: jax.Array, cfg, patch_embeds=None) -> jax.Array:
    h, _ = hidden_states(params, tokens, cfg, patch_embeds)
    return unembed(params, h, cfg)


def loss_fn(params: dict, batch: dict, cfg):
    """batch: tokens (B,S) int32, labels (B,S) int32 (-1 = masked).
    Returns (loss, metrics)."""
    h, aux = hidden_states(params, batch["tokens"], cfg, batch.get("patch_embeds"))
    if cfg.frontend == "vision" and "patch_embeds" in batch:
        h = h[:, batch["patch_embeds"].shape[1]:]  # loss over text positions only
    xent = chunked_xent(params, h, batch["labels"], cfg)
    loss = xent + 0.01 * aux
    return loss, {"loss": loss, "xent": xent, "moe_aux": aux}


def prefill(params: dict, tokens: jax.Array, cfg, cache_capacity: int, patch_embeds=None):
    """Returns (last-position logits (B, V), cache)."""
    h = embed_tokens(params, tokens, cfg)
    if cfg.frontend == "vision" and patch_embeds is not None:
        fp = fsdp.gather_leaf(params["frontend_proj"], ("embed", "embed_out"))
        pe = jnp.einsum("bsd,de->bse", patch_embeds.astype(h.dtype), cast(fp, h.dtype))
        h = jnp.concatenate([pe, h], axis=1)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    bschema = block_schema(cfg)

    def body(hh, lp):
        hh, (k, v), _ = decoder_block(fsdp.gather(lp, bschema), hh, positions, cfg)
        pad = cache_capacity - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return hh, {"k": kc.astype(jnp.dtype(cfg.dtype)), "v": vc.astype(jnp.dtype(cfg.dtype))}

    h, cache = jax.lax.scan(body, h, params["layers"])
    logits = unembed(params, h[:, -1:], cfg)[:, 0]
    return logits, cache


def decode_step(params: dict, token: jax.Array, cache: dict, cache_len: jax.Array, cfg):
    """token: (B, 1) int32; cache: {'k','v'} stacked (L, B, cap, KV, hd).
    ``cache_len`` is a scalar (all lanes aligned) or a (B,) vector of
    per-lane lengths (continuous batching: lanes decode at ragged
    positions).  Returns (logits (B, V), new cache)."""
    h = embed_tokens(params, token, cfg)

    bschema = block_schema(cfg)

    def body(hh, xs):
        lp, c = xs
        lp = fsdp.gather(lp, bschema)
        hh, kc, vc = decoder_block_decode(lp, hh, c["k"], c["v"], cache_len, cfg)
        return hh, {"k": kc, "v": vc}

    h, new_cache = jax.lax.scan(body, h, (params["layers"], cache))
    logits = unembed(params, h, cfg)[:, 0]
    return logits, new_cache


def cache_schema(cfg, batch: int, capacity: int) -> dict:
    """Abstract KV-cache layout (used by input_specs + serving engine)."""
    KV, hd, L = cfg.num_kv_heads, cfg.d_head, cfg.num_layers
    spec = ParamSpec(
        (L, batch, capacity, KV, hd),
        ("layers", "act_batch", "act_kv_seq", "kv_heads", "head_dim"),
        init="zeros",
        dtype=cfg.dtype,
    )
    return {"k": spec, "v": spec}
