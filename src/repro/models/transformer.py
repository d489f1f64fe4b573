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

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import (
    ParamSpec,
    PathCounter,
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
LOSS_CHUNK = 512  # sequence positions per logits block in ``head_xent``
# a chip's bytes of stacked logit gradients per contraction for the head's
# gradient in ``head_xent``: every benchmarked shape takes one contraction
LOSS_STASH_BYTES = 2 << 30
# the path ``head_xent`` takes: ``fused`` (gradients in the forward pass) or
# ``plain`` (the forward alone)
count_loss_paths = PathCounter()


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
    (-1 = masked): the LM loss of every decoder family, under the named
    scope ``loss_head``.  The final norm and the head's weight are taken
    here, where autodiff transposes them; ``head_xent`` does the rest."""
    with jax.named_scope("loss_head"):
        h = rms_norm(h, params["final_norm"], cfg.norm_eps)
        eq, w = _head(params, cfg, h.dtype)
        total, count = head_xent(h, w, labels, eq)
        return total / jnp.maximum(count, 1.0)


def head_xent(h, w, labels, eq, stash_bytes=LOSS_STASH_BYTES):
    """(summed nll, count of unmasked labels) of the logits ``einsum(eq, h,
    w)``, in blocks of at most ``LOSS_CHUNK`` positions, so only one block's
    f32 logits is live at a time; whole-sequence logits and their gradient at
    a 152k vocabulary and 4096 positions take ~7 GB, half of a 16 GB chip.

    Differentiated, the forward pass forms the gradients: each block's logit
    gradient gives its part of ``dh`` at once and is stacked, and the head's
    gradient is one contraction of the stack over every position, or one per
    group of blocks where a chip's share of the stack would pass
    ``stash_bytes`` (padding with masked positions to whole groups).  The
    backward pass only scales the two.  ``count_loss_paths`` counts ``fused``
    for this and ``plain`` for the forward alone."""
    B, S, _ = h.shape
    chunk = min(S, LOSS_CHUNK)
    blocks = -(-S // chunk)
    vocab = w.shape[_w_axes(eq).index("v")]
    # a chip's share of one block's logit gradient, stacked at w's dtype
    block_bytes = (B * chunk * vocab * w.dtype.itemsize
                   / (fsdp.group_count("act_batch") * fsdp.group_count("vocab")))
    groups = max(1, math.ceil(blocks * block_bytes / stash_bytes))
    pad = groups * -(-blocks // groups) * chunk - S
    if pad:
        h = jnp.pad(h, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    count = jnp.sum(labels >= 0).astype(jnp.float32)
    return _nll_sum(eq, chunk, groups, h, w, labels), count


def _w_axes(eq):
    """The head weight's axes in ``eq``: 'vd' tied, 'dv' untied."""
    return eq.split(",")[1].split("->")[0]


def _blocks(x, chunk):
    """(B, S, ...) -> (S // chunk, B, chunk, ...)."""
    B, S = x.shape[:2]
    return jnp.swapaxes(x.reshape(B, S // chunk, chunk, *x.shape[2:]), 0, 1)


def _block_logits(eq, hb, w, lb):
    """One block's f32 logits, log-sum-exp and summed nll.  The gold logit
    is picked before the cast, so no f32 copy of the logits is written."""
    logits = jnp.einsum(eq, hb, w)
    gold = jnp.take_along_axis(logits, jnp.maximum(lb, 0)[..., None], axis=-1)
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    nll = lse - gold[..., 0].astype(jnp.float32)
    return logits, lse, jnp.sum(jnp.where(lb >= 0, nll, 0.0))


@partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _nll_sum(eq, chunk, groups, h, w, labels):
    count_loss_paths.add("plain")

    def block(total, xs):
        return total + _block_logits(eq, xs[0], w, xs[1])[2], None

    total, _ = jax.lax.scan(block, jnp.zeros((), jnp.float32),
                            (_blocks(h, chunk), _blocks(labels, chunk)))
    return total


def _nll_sum_fwd(eq, chunk, groups, h, w, labels):
    count_loss_paths.add("fused")
    B, S, D = h.shape
    dh_eq = f"bsv,{_w_axes(eq)}->bsd"
    dw_eq = f"nbsv,nbsd->{_w_axes(eq)}"

    def block(total, xs):
        hb, lb = xs
        logits, lse, nll = _block_logits(eq, hb, w, lb)
        onehot = lb[..., None] == jnp.arange(logits.shape[-1])
        dlogits = jnp.where((lb >= 0)[..., None],
                            jnp.exp(logits - lse[..., None]) - onehot, 0.0)
        dlogits = dlogits.astype(w.dtype)
        return total + nll, (jnp.einsum(dh_eq, dlogits, w), dlogits)

    def group(carry, xs):
        total, dw = carry
        total, (dh, dlogits) = jax.lax.scan(block, total, xs)
        dw += jnp.einsum(dw_eq, dlogits, xs[0],
                         preferred_element_type=jnp.float32)
        return (total, dw), dh

    hs = _blocks(h, chunk).reshape(groups, -1, B, chunk, D)
    ls = _blocks(labels, chunk).reshape(groups, -1, B, chunk)
    # with one group XLA drops this loop and its f32 accumulator
    init = jnp.zeros((), jnp.float32), jnp.zeros(w.shape, jnp.float32)
    (total, dw), dh = jax.lax.scan(group, init, (hs, ls))
    dh = jnp.swapaxes(dh.reshape(-1, B, chunk, D), 0, 1).reshape(B, S, D)
    # made here, not fused into their late consumers (the optimizer's update
    # of a tied embedding), so the stack is freed before the backward pass
    return total, jax.lax.optimization_barrier((dh, dw.astype(w.dtype)))


def _nll_sum_bwd(eq, chunk, groups, res, ct):
    dh, dw = res
    return (dh * ct).astype(dh.dtype), (dw * ct).astype(dw.dtype), None


_nll_sum.defvjp(_nll_sum_fwd, _nll_sum_bwd)


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
