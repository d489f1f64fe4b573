"""zamba2 hybrid stack: Mamba-2 backbone + ONE shared attention block.

The shared block's weights are used at every ``attn_every``-th layer (weight
sharing across invocations — the zamba2 signature).  Its input is
concat(hidden, first-layer embedding) (2·d_model), attention output projects
back to d_model, followed by a gated MLP.  [arXiv:2411.15242]
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import attention as attn_lib
from repro.models.layers import (
    ParamSpec,
    apply_rope,
    cast,
    mlp_apply,
    mlp_schema,
    rms_norm,
    stack_schema,
)
from repro.models.mamba2 import mamba2_apply, mamba2_schema
from repro.dist import fsdp
from repro.models.transformer import chunked_xent, embed_tokens, unembed


def shared_block_schema(cfg) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.d_head
    D2 = 2 * D
    return {
        "ln_in": ParamSpec((D2,), ("norm",), init="zeros"),
        "wq": ParamSpec((D2, H, hd), ("embed", "heads", "head_dim")),
        "wk": ParamSpec((D2, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wv": ParamSpec((D2, KV, hd), ("embed", "kv_heads", "head_dim")),
        "wo": ParamSpec((H, hd, D), ("heads", "head_dim", "embed")),
        "ln_mlp": ParamSpec((D,), ("norm",), init="zeros"),
        "mlp": mlp_schema(cfg),
    }


def hybrid_schema(cfg) -> dict:
    D, Vp = cfg.d_model, cfg.padded_vocab
    layer = {
        "ln": ParamSpec((D,), ("norm",), init="zeros"),
        "mamba": mamba2_schema(cfg),
    }
    schema = {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), init="embed"),
        "layers": stack_schema(layer, cfg.num_layers),
        "shared": shared_block_schema(cfg),
        "final_norm": ParamSpec((D,), ("norm",), init="zeros"),
    }
    return schema


def n_shared_invocations(cfg) -> int:
    return (cfg.num_layers + cfg.attn_every - 1) // cfg.attn_every


def _shared_qkv(sp: dict, xcat: jax.Array, positions: jax.Array, cfg):
    dt = xcat.dtype
    a_in = rms_norm(xcat, sp["ln_in"], cfg.norm_eps)
    q = jnp.einsum("bsd,dhk->bshk", a_in, cast(sp["wq"], dt))
    k = jnp.einsum("bsd,dhk->bshk", a_in, cast(sp["wk"], dt))
    v = jnp.einsum("bsd,dhk->bshk", a_in, cast(sp["wv"], dt))
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    return q, k, v


def shared_block(sp: dict, h: jax.Array, h0: jax.Array, positions: jax.Array, cfg):
    xcat = jnp.concatenate([h, h0], axis=-1)
    q, k, v = _shared_qkv(sp, xcat, positions, cfg)
    attn_out = attn_lib.attend(q, k, v, causal=True, window=cfg.sliding_window)
    h = h + jnp.einsum("bshk,hkd->bsd", attn_out, cast(sp["wo"], h.dtype))
    m_in = rms_norm(h, sp["ln_mlp"], cfg.norm_eps)
    return h + mlp_apply(sp["mlp"], m_in)


def hidden_states(params: dict, tokens: jax.Array, cfg):
    h = embed_tokens(params, tokens, cfg)
    h0 = h
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    sp = fsdp.gather(params["shared"], shared_block_schema(cfg))
    lschema = {"ln": ParamSpec((cfg.d_model,), ("norm",), init="zeros"),
               "mamba": mamba2_schema(cfg)}

    def block(lp_idx, hh):
        lp, idx = lp_idx
        lp = fsdp.gather(lp, lschema)
        m_in = rms_norm(hh, lp["ln"], cfg.norm_eps)
        m_out, _ = mamba2_apply(lp["mamba"], m_in, cfg)
        hh = hh + m_out
        hh = jax.lax.cond(
            idx % cfg.attn_every == 0,
            lambda x: shared_block(sp, x, h0, positions, cfg),
            lambda x: x,
            hh,
        )
        return hh

    blk = jax.checkpoint(block) if cfg.remat_policy != "none" else block

    def body(hh, xs):
        return blk(xs, hh), None

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    h, _ = jax.lax.scan(body, h, (params["layers"], idxs))
    return h


def forward(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    return unembed(params, hidden_states(params, tokens, cfg), cfg)


def loss_fn(params: dict, batch: dict, cfg):
    h = hidden_states(params, batch["tokens"], cfg)
    xent = chunked_xent(params, h, batch["labels"], cfg)
    return xent, {"loss": xent, "xent": xent}


# ---------------------------------------------------------------------------
# Decode (serving): Mamba states per layer + shared-block KV caches per
# invocation + the cached first-layer embedding h0 for the concat input.
# ---------------------------------------------------------------------------


def cache_schema(cfg, batch: int, capacity: int) -> dict:
    H, P, N = cfg.ssm_heads, cfg.ssm_head_dim, cfg.ssm_state
    KV, hd = cfg.num_kv_heads, cfg.d_head
    L, NS = cfg.num_layers, n_shared_invocations(cfg)
    return {
        "ssm": ParamSpec(
            (L, batch, H, P, N), ("layers", "act_batch", "heads", "head_dim", "ssm_state"),
            init="zeros", dtype="float32",
        ),
        "conv": ParamSpec(
            (L, batch, cfg.ssm_conv - 1, cfg.ssm_d_inner),
            ("layers", "act_batch", "conv_k", "ssm_inner"),
            init="zeros", dtype=cfg.dtype,
        ),
        "k": ParamSpec(
            (NS, batch, capacity, KV, hd),
            ("layers", "act_batch", "act_kv_seq", "kv_heads", "head_dim"),
            init="zeros", dtype=cfg.dtype,
        ),
        "v": ParamSpec(
            (NS, batch, capacity, KV, hd),
            ("layers", "act_batch", "act_kv_seq", "kv_heads", "head_dim"),
            init="zeros", dtype=cfg.dtype,
        ),
    }


def _shared_block_decode(sp, h, h0, k_all, v_all, slot, cache_len, cfg):
    """One shared-attention invocation at decode time. k_all/v_all stacked
    (NS, B, cap, KV, hd); slot selects the invocation's cache."""
    positions = jnp.full((h.shape[0], 1), cache_len, dtype=jnp.int32)
    xcat = jnp.concatenate([h, h0], axis=-1)
    q, k, v = _shared_qkv(sp, xcat, positions, cfg)
    kc = jax.lax.dynamic_index_in_dim(k_all, slot, 0, keepdims=False)
    vc = jax.lax.dynamic_index_in_dim(v_all, slot, 0, keepdims=False)
    kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), cache_len, 1)
    vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), cache_len, 1)
    attn_out = attn_lib.decode_attention(
        q, kc.astype(q.dtype), vc.astype(q.dtype), cache_len + 1, window=cfg.sliding_window
    )
    h = h + jnp.einsum("bshk,hkd->bsd", attn_out, cast(sp["wo"], h.dtype))
    m_in = rms_norm(h, sp["ln_mlp"], cfg.norm_eps)
    h = h + mlp_apply(sp["mlp"], m_in)
    k_all = jax.lax.dynamic_update_index_in_dim(k_all, kc, slot, 0)
    v_all = jax.lax.dynamic_update_index_in_dim(v_all, vc, slot, 0)
    return h, k_all, v_all


def decode_step(params: dict, token: jax.Array, cache: dict, cache_len: jax.Array, cfg):
    h = embed_tokens(params, token, cfg)
    # the shared block's concat input uses the CURRENT position's embedding
    # (matches forward(), where h0[t] = embed(tokens[t]))
    h0 = h
    sp = fsdp.gather(params["shared"], shared_block_schema(cfg))
    lschema = {"ln": ParamSpec((cfg.d_model,), ("norm",), init="zeros"),
               "mamba": mamba2_schema(cfg)}

    def body(carry, xs):
        hh, k_all, v_all = carry
        lp, idx, ssm_state, conv_state = xs
        lp = fsdp.gather(lp, lschema)
        m_in = rms_norm(hh, lp["ln"], cfg.norm_eps)
        m_out, (new_state, new_conv) = mamba2_apply(
            lp["mamba"], m_in, cfg, state=(ssm_state, conv_state), decode=True)
        hh = hh + m_out

        def with_attn(args):
            hh, k_all, v_all = args
            return _shared_block_decode(
                sp, hh, h0, k_all, v_all, idx // cfg.attn_every, cache_len, cfg
            )

        hh, k_all, v_all = jax.lax.cond(
            idx % cfg.attn_every == 0, with_attn, lambda a: a, (hh, k_all, v_all)
        )
        return (hh, k_all, v_all), (new_state, new_conv.astype(conv_state.dtype))

    idxs = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    (h, k_all, v_all), (ssm_new, conv_new) = jax.lax.scan(
        body, (h, cache["k"], cache["v"]),
        (params["layers"], idxs, cache["ssm"], cache["conv"]),
    )
    logits = unembed(params, h, cfg)[:, 0]
    new_cache = {"ssm": ssm_new, "conv": conv_new, "k": k_all, "v": v_all}
    return logits, new_cache
