"""Plain float32 reference of a dense decoder: its parameter layout, the
law of its seeded weights, its loss, and the FLOPs its training step
requires.

Written from the configuration file alone (HF key names) and importing
nothing of the program: RMSNorm with a (1 + scale) gain, rotary embeddings
on two halves, causal GQA/MHA attention with optional QKV bias, a SwiGLU
MLP, a tied or untied head over the padded vocabulary, and the mean
next-token loss.  Matmuls run at ``Precision.HIGHEST``; attention is
computed in blocks of query rows and each layer under ``jax.checkpoint``,
so that it fits beside nothing else on the cell's chips.  The parameter
layout is the program's: per-layer leaves stacked on a leading layers axis,
so the same seeded weights go into both.

``prec="fp8"`` is the control: every matmul operand rounded to float8
(e4m3, one scale per tensor), one step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench.inputs import fan_in_std, normal
from chipbench.reference import HI, mm

BLOCK = 512  # query rows per attention block, positions per loss block


def padded_vocab(c: dict) -> int:
    m = c.get("vocab_pad_multiple", 1)
    return -(-c["vocab_size"] // m) * m


def param_shapes(c: dict) -> dict:
    D, F, L = c["hidden_size"], c["intermediate_size"], c["num_hidden_layers"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32)  # noqa: E731
    attn = {"wq": f32(L, D, H, hd), "wk": f32(L, D, KV, hd),
            "wv": f32(L, D, KV, hd), "wo": f32(L, H, hd, D)}
    if c["attention_bias"]:
        attn.update(bq=f32(L, H, hd), bk=f32(L, KV, hd), bv=f32(L, KV, hd))
    shapes = {
        "embed": f32(padded_vocab(c), D),
        "final_norm": f32(D),
        "layers": {"attn": attn, "ln1": f32(L, D), "ln2": f32(L, D),
                   "mlp": {"wg": f32(L, D, F), "wu": f32(L, D, F),
                           "wd": f32(L, F, D)}},
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = f32(D, padded_vocab(c))
    return shapes


# Initial std of each leaf, by its key.  Matrices take 1/sqrt(fan-in), where
# the fan-in is the product of the listed dims of the stacked (layers-first)
# leaf; the rest take a fixed std.  Norm scales are offsets from 1 and
# biases are non-zero, so that both take part in the comparison.
FAN_IN_DIMS = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
               "wg": (1,), "wu": (1,), "wd": (1,)}
FIXED_STD = {"embed": 0.02, "lm_head": 0.02, "ln1": 0.02, "ln2": 0.02,
             "final_norm": 0.02, "bq": 0.02, "bk": 0.02, "bv": 0.02}


def init(path: str, key, shape: tuple):
    """The seeded weight at ``path`` (``layers/attn/wo``): normal, with
    the std of its key.  Each key names one leaf of this layout."""
    name = path.rsplit("/", 1)[-1]
    if name in FIXED_STD:
        return normal(key, shape, FIXED_STD[name])
    if name not in FAN_IN_DIMS:
        raise KeyError(f"no initial law for a leaf at {path!r}")
    return normal(key, shape, fan_in_std(shape, FAN_IN_DIMS[name]))


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (
        1.0 + scale)


def _rope(x, theta):
    S, hd = x.shape[1], x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs  # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v):
    """Causal softmax attention, query rows in blocks.  q (B,S,H,hd), k and
    v (B,S,KV,hd); query head h reads kv head h // (H / KV)."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    qb = BLOCK if S % BLOCK == 0 else S
    nb = S // qb
    qg = (q / math.sqrt(hd)).reshape(B, nb, qb, KV, H // KV, hd)
    kpos = jnp.arange(S)

    @jax.checkpoint
    def block(i, qi):
        s = jnp.einsum("bqngh,bknh->bngqk", qi, k, precision=HI)
        seen = kpos[None, :] <= (i * qb + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return jnp.einsum("bngqk,bknh->bqngh", p, v, precision=HI)

    out = jax.lax.map(lambda a: block(*a), (jnp.arange(nb),
                                            jnp.moveaxis(qg, 1, 0)))
    return jnp.moveaxis(out, 0, 1).reshape(B, S, H, hd)


def _layer(h, p, c, prec):
    eps, a = c["rms_norm_eps"], p["attn"]
    x = _rms_norm(h, p["ln1"], eps)
    q = mm("bsd,dhk->bshk", x, a["wq"], prec)
    k = mm("bsd,dhk->bshk", x, a["wk"], prec)
    v = mm("bsd,dhk->bshk", x, a["wv"], prec)
    if c["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    h = h + mm("bshk,hkd->bsd", _attention(q, k, v), a["wo"], prec)
    x = _rms_norm(h, p["ln2"], eps)
    m = p["mlp"]
    g = mm("bsd,df->bsf", x, m["wg"], prec)
    u = mm("bsd,df->bsf", x, m["wu"], prec)
    return h + mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wd"], prec)


def loss(params, batch, c: dict, prec: str = "f32"):
    """Mean next-token cross-entropy over every position of the batch."""
    tokens, labels = batch["tokens"], batch["labels"]
    layer = jax.checkpoint(partial(_layer, c=c, prec=prec))
    h = params["embed"][tokens]
    h, _ = jax.lax.scan(lambda hh, lp: (layer(hh, lp), None), h,
                        params["layers"])
    h = _rms_norm(h, params["final_norm"], c["rms_norm_eps"])
    if c["tie_word_embeddings"]:
        eq, w = "bsd,vd->bsv", params["embed"]
    else:
        eq, w = "bsd,dv->bsv", params["lm_head"]
    B, S, D = h.shape
    lb = BLOCK if S % BLOCK == 0 else S

    @jax.checkpoint
    def block(hb, yb):
        logits = mm(eq, hb, w, prec)
        gold = jnp.take_along_axis(logits, yb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    hs = jnp.moveaxis(h.reshape(B, S // lb, lb, D), 1, 0)
    ys = jnp.moveaxis(labels.reshape(B, S // lb, lb), 1, 0)
    return jnp.sum(jax.lax.map(lambda a: block(*a), (hs, ys))) / (B * S)


# -- the FLOPs a training step requires, per token --------------------------
#
# Required means what forward and backward must compute: 6 FLOPs per token
# for every matmul parameter (2 forward, 4 backward), plus causal
# attention's score and value products over the positions each query sees.
# Recomputation (remat, the blocked loss's second head pass) and the masked
# half of blocked attention are not counted.  The head is counted over the
# real vocabulary, not the padded one, and a tied embedding is counted
# once, as the head.

def matmul_params(c: dict) -> int:
    """Matmul parameters of the configuration file ``c`` (HF keys)."""
    D, F = c["hidden_size"], c["intermediate_size"]
    H, KV, hd = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    per_layer = D * H * hd + 2 * D * KV * hd + H * hd * D + 3 * D * F
    head = c["vocab_size"] * D  # the input embedding is a lookup
    return c["num_hidden_layers"] * per_layer + head


def attention_flops_per_token(c: dict, seq: int) -> float:
    """Forward + backward of QK^T and PV under a causal mask: a query at
    position i sees i + 1 keys, so on average (seq + 1) / 2; each product
    is 2 FLOPs per key per head dim forward and twice that backward."""
    H, hd, L = c["num_attention_heads"], c["head_dim"], c["num_hidden_layers"]
    mean_keys = (seq + 1) / 2
    return 3 * L * 2 * (2 * H * hd * mean_keys)


def train_flops_per_token(c: dict, seq: int) -> float:
    return 6 * matmul_params(c) + attention_flops_per_token(c, seq)
