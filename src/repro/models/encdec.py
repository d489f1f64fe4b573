"""Encoder-decoder stack (seamless-m4t-large-v2 backbone).

The speech frontend is a STUB per the assignment: inputs are precomputed
frame embeddings (B, S_enc, D).  Encoder: bidirectional self-attention.
Decoder: causal self-attention + cross-attention over encoder output.

Shape mapping (DESIGN.md §4): for train/prefill cells the encoder consumes
seq_len frames and the decoder seq_len // DEC_RATIO tokens; decode cells run
one decoder step against a decoder KV cache of seq_len with a cached encoder
memory of ENC_MEMORY frames.
"""
from __future__ import annotations

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
from repro.models.transformer import chunked_xent, embed_tokens, unembed
from repro.dist import fsdp

DEC_RATIO = 4       # decoder length = encoder length // 4 for train/prefill
ENC_MEMORY = 4096   # encoder memory length at decode shapes


def encoder_block_schema(cfg) -> dict:
    D = cfg.d_model
    return {
        "ln1": ParamSpec((D,), ("norm",), init="zeros"),
        "ln2": ParamSpec((D,), ("norm",), init="zeros"),
        "attn": attention_schema(cfg),
        "mlp": mlp_schema(cfg),
    }


def decoder_block_schema(cfg) -> dict:
    D = cfg.d_model
    return {
        "ln1": ParamSpec((D,), ("norm",), init="zeros"),
        "lnx": ParamSpec((D,), ("norm",), init="zeros"),
        "ln2": ParamSpec((D,), ("norm",), init="zeros"),
        "self_attn": attention_schema(cfg),
        "cross_attn": attention_schema(cfg),
        "mlp": mlp_schema(cfg),
    }


def encdec_schema(cfg) -> dict:
    D, Vp = cfg.d_model, cfg.padded_vocab
    return {
        "frontend_proj": ParamSpec((D, D), ("embed", "embed_out")),
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), init="embed"),
        "enc_layers": stack_schema(encoder_block_schema(cfg), cfg.num_encoder_layers),
        "dec_layers": stack_schema(decoder_block_schema(cfg), cfg.num_layers),
        "enc_norm": ParamSpec((D,), ("norm",), init="zeros"),
        "final_norm": ParamSpec((D,), ("norm",), init="zeros"),
        "lm_head": ParamSpec((D, Vp), ("embed", "vocab")),
    }


def _enc_block(lp, h, positions, cfg):
    lp = fsdp.gather(lp, encoder_block_schema(cfg))
    a_in = rms_norm(h, lp["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(lp["attn"], a_in, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    h = h + out_project(lp["attn"], attn_lib.attend(q, k, v, causal=False))
    m_in = rms_norm(h, lp["ln2"], cfg.norm_eps)
    return h + mlp_apply(lp["mlp"], m_in)


def encode(params: dict, frames: jax.Array, cfg) -> jax.Array:
    """frames: (B, S_enc, D) precomputed frame embeddings (frontend stub)."""
    dt = jnp.dtype(cfg.dtype)
    fp = fsdp.gather_leaf(params["frontend_proj"], ("embed", "embed_out"))
    h = jnp.einsum("bsd,de->bse", frames.astype(dt), cast(fp, dt))
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    blk = (
        jax.checkpoint(lambda lp, hh: _enc_block(lp, hh, positions, cfg))
        if cfg.remat_policy != "none"
        else (lambda lp, hh: _enc_block(lp, hh, positions, cfg))
    )

    def body(hh, lp):
        return blk(lp, hh), None

    h, _ = jax.lax.scan(body, h, params["enc_layers"])
    return rms_norm(h, params["enc_norm"], cfg.norm_eps)


def _cross_attend(lp, h, enc_kv, positions, cfg):
    """Cross-attention: q from decoder h, k/v precomputed from encoder."""
    a_in = rms_norm(h, lp["lnx"], cfg.norm_eps)
    dt = h.dtype
    q = jnp.einsum("bsd,dhk->bshk", a_in, cast(lp["cross_attn"]["wq"], dt))
    k, v = enc_kv
    return h + out_project(
        lp["cross_attn"], attn_lib.attend(q, k, v, causal=False)
    )


def _enc_kv(lp, enc_out, cfg):
    dt = enc_out.dtype
    k = jnp.einsum("bsd,dhk->bshk", enc_out, cast(lp["cross_attn"]["wk"], dt))
    v = jnp.einsum("bsd,dhk->bshk", enc_out, cast(lp["cross_attn"]["wv"], dt))
    return k, v


def _dec_block(lp, h, enc_out, positions, cfg):
    lp = fsdp.gather(lp, decoder_block_schema(cfg))
    a_in = rms_norm(h, lp["ln1"], cfg.norm_eps)
    q, k, v = qkv_project(lp["self_attn"], a_in, cfg)
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)
    h = h + out_project(lp["self_attn"], attn_lib.attend(q, k, v, causal=True))
    h = _cross_attend(lp, h, _enc_kv(lp, enc_out, cfg), positions, cfg)
    m_in = rms_norm(h, lp["ln2"], cfg.norm_eps)
    return h + mlp_apply(lp["mlp"], m_in)


def decoder_hidden(params: dict, frames: jax.Array, tokens: jax.Array, cfg) -> jax.Array:
    enc_out = encode(params, frames, cfg)
    h = embed_tokens(params, tokens, cfg)
    B, S, _ = h.shape
    positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    blk = (
        jax.checkpoint(lambda lp, hh: _dec_block(lp, hh, enc_out, positions, cfg))
        if cfg.remat_policy != "none"
        else (lambda lp, hh: _dec_block(lp, hh, enc_out, positions, cfg))
    )

    def body(hh, lp):
        return blk(lp, hh), None

    h, _ = jax.lax.scan(body, h, params["dec_layers"])
    return h


def forward(params: dict, frames: jax.Array, tokens: jax.Array, cfg) -> jax.Array:
    return unembed(params, decoder_hidden(params, frames, tokens, cfg), cfg)


def loss_fn(params: dict, batch: dict, cfg):
    h = decoder_hidden(params, batch["frames"], batch["tokens"], cfg)
    xent = chunked_xent(params, h, batch["labels"], cfg)
    return xent, {"loss": xent, "xent": xent}


def cache_schema(cfg, batch: int, capacity: int) -> dict:
    KV, hd, L = cfg.num_kv_heads, cfg.d_head, cfg.num_layers
    kv = ParamSpec(
        (L, batch, capacity, KV, hd),
        ("layers", "act_batch", "act_kv_seq", "kv_heads", "head_dim"),
        init="zeros", dtype=cfg.dtype,
    )
    enc_kv = ParamSpec(
        (L, batch, ENC_MEMORY, KV, hd),
        ("layers", "act_batch", "act_kv_seq", "kv_heads", "head_dim"),
        init="zeros", dtype=cfg.dtype,
    )
    return {"k": kv, "v": kv, "enc_k": enc_kv, "enc_v": enc_kv}


def decode_step(params: dict, token: jax.Array, cache: dict, cache_len: jax.Array, cfg):
    """One decoder step; encoder memory K/V precomputed in the cache."""
    h = embed_tokens(params, token, cfg)

    def body(hh, xs):
        lp, c = xs
        lp = fsdp.gather(lp, decoder_block_schema(cfg))
        positions = jnp.full((hh.shape[0], 1), cache_len, dtype=jnp.int32)
        a_in = rms_norm(hh, lp["ln1"], cfg.norm_eps)
        q, k, v = qkv_project(lp["self_attn"], a_in, cfg)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
        kc = jax.lax.dynamic_update_slice_in_dim(c["k"], k.astype(c["k"].dtype), cache_len, 1)
        vc = jax.lax.dynamic_update_slice_in_dim(c["v"], v.astype(c["v"].dtype), cache_len, 1)
        hh = hh + out_project(
            lp["self_attn"],
            attn_lib.decode_attention(q, kc.astype(q.dtype), vc.astype(q.dtype), cache_len + 1),
        )
        # cross-attention over full encoder memory
        x_in = rms_norm(hh, lp["lnx"], cfg.norm_eps)
        qx = jnp.einsum("bsd,dhk->bshk", x_in, cast(lp["cross_attn"]["wq"], hh.dtype))
        hh = hh + out_project(
            lp["cross_attn"],
            attn_lib.decode_attention(
                qx, c["enc_k"].astype(qx.dtype), c["enc_v"].astype(qx.dtype),
                jnp.int32(ENC_MEMORY),
            ),
        )
        m_in = rms_norm(hh, lp["ln2"], cfg.norm_eps)
        hh = hh + mlp_apply(lp["mlp"], m_in)
        return hh, {"k": kc, "v": vc, "enc_k": c["enc_k"], "enc_v": c["enc_v"]}

    h, new_cache = jax.lax.scan(body, h, (params["dec_layers"], cache))
    logits = unembed(params, h, cfg)[:, 0]
    return logits, new_cache
