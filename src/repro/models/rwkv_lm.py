"""RWKV-6 LM stack (rwkv6-1.6b). Attention-free; O(1) decode state."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models.layers import ParamSpec, rms_norm, stack_schema
from repro.models.rwkv6 import (
    rwkv6_channel_mix,
    rwkv6_schema,
    rwkv6_time_mix,
)
from repro.models.transformer import chunked_xent, embed_tokens, unembed
from repro.dist import fsdp


def _layer_schema(cfg) -> dict:
    D = cfg.d_model
    return {
        "ln1": ParamSpec((D,), ("norm",), init="zeros"),
        "ln2": ParamSpec((D,), ("norm",), init="zeros"),
        "mix": rwkv6_schema(cfg),
    }


def rwkv_lm_schema(cfg) -> dict:
    D, Vp = cfg.d_model, cfg.padded_vocab
    layer = {
        "ln1": ParamSpec((D,), ("norm",), init="zeros"),
        "ln2": ParamSpec((D,), ("norm",), init="zeros"),
        "mix": rwkv6_schema(cfg),
    }
    return {
        "embed": ParamSpec((Vp, D), ("vocab", "embed"), init="embed"),
        "layers": stack_schema(layer, cfg.num_layers),
        "final_norm": ParamSpec((D,), ("norm",), init="zeros"),
        "lm_head": ParamSpec((D, Vp), ("embed", "vocab")),
    }


def _block(lp, h, cfg, decode=False, states=None):
    lp = fsdp.gather(lp, _layer_schema(cfg))
    tm_in = rms_norm(h, lp["ln1"], cfg.norm_eps)
    if decode:
        wkv_state, tm_shift, cm_shift = states
        tm_out, wkv_new, tm_last = rwkv6_time_mix(
            lp["mix"], tm_in, cfg, state=wkv_state, decode=True,
            shift_state=tm_shift,
        )
    else:
        tm_out, wkv_new, tm_last = rwkv6_time_mix(lp["mix"], tm_in, cfg)
    h = h + tm_out
    cm_in = rms_norm(h, lp["ln2"], cfg.norm_eps)
    if decode:
        cm_out, cm_last = rwkv6_channel_mix(lp["mix"], cm_in, shift_state=cm_shift)
    else:
        cm_out, cm_last = rwkv6_channel_mix(lp["mix"], cm_in)
    h = h + cm_out
    return h, (wkv_new, tm_last, cm_last)


def hidden_states(params: dict, tokens: jax.Array, cfg):
    h = embed_tokens(params, tokens, cfg)

    blk = (
        jax.checkpoint(lambda lp, hh: _block(lp, hh, cfg))
        if cfg.remat_policy != "none"
        else (lambda lp, hh: _block(lp, hh, cfg))
    )

    def body(hh, lp):
        hh, _ = blk(lp, hh)
        return hh, None

    h, _ = jax.lax.scan(body, h, params["layers"])
    return h


def forward(params: dict, tokens: jax.Array, cfg) -> jax.Array:
    return unembed(params, hidden_states(params, tokens, cfg), cfg)


def loss_fn(params: dict, batch: dict, cfg):
    h = hidden_states(params, batch["tokens"], cfg)
    xent = chunked_xent(params, h, batch["labels"], cfg)
    return xent, {"loss": xent, "xent": xent}


def cache_schema(cfg, batch: int, capacity: int) -> dict:
    """O(1) state — `capacity` is ignored (kept for API uniformity)."""
    H, hd, D, L = cfg.num_heads, cfg.d_head, cfg.d_model, cfg.num_layers
    return {
        "wkv": ParamSpec(
            (L, batch, H, hd, hd),
            ("layers", "act_batch", "heads", "head_dim", "head_dim2"),
            init="zeros", dtype="float32",
        ),
        "tm_shift": ParamSpec(
            (L, batch, D), ("layers", "act_batch", "act_embed"), init="zeros",
            dtype=cfg.dtype,
        ),
        "cm_shift": ParamSpec(
            (L, batch, D), ("layers", "act_batch", "act_embed"), init="zeros",
            dtype=cfg.dtype,
        ),
    }


def decode_step(params: dict, token: jax.Array, cache: dict, cache_len: jax.Array, cfg):
    del cache_len  # O(1) state — position-free
    h = embed_tokens(params, token, cfg)

    def body(hh, xs):
        lp, wkv, tms, cms = xs
        hh, (wkv_new, tm_last, cm_last) = _block(
            lp, hh, cfg, decode=True, states=(wkv, tms.astype(hh.dtype), cms.astype(hh.dtype))
        )
        return hh, (wkv_new, tm_last.astype(tms.dtype), cm_last.astype(cms.dtype))

    h, (wkv, tms, cms) = jax.lax.scan(
        body, h, (params["layers"], cache["wkv"], cache["tm_shift"], cache["cm_shift"])
    )
    logits = unembed(params, h, cfg)[:, 0]
    return logits, {"wkv": wkv, "tm_shift": tms, "cm_shift": cms}
