"""Plain float32 reference of the dense decoder's training step.

Written from the configuration file alone (HF key names) and importing
nothing of the program: RMSNorm with a (1 + scale) gain, rotary embeddings
on two halves, causal GQA/MHA attention with optional QKV bias, a SwiGLU
MLP, a tied or untied head over the padded vocabulary, the mean next-token
loss, global-norm clipping and AdamW.  Matmuls run at
``Precision.HIGHEST``; attention is computed in blocks of query rows and
each layer under ``jax.checkpoint``, so that it fits beside nothing else on
one chip.  The parameter layout is the program's: per-layer leaves stacked
on a leading layers axis, so the same seeded weights go into both.

``prec="fp8"`` is the control: every matmul operand rounded to float8
(e4m3, one scale per tensor), one step below the configuration's bfloat16.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp

from chipbench import inputs

HI = jax.lax.Precision.HIGHEST
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


def _round(x, prec: str):
    if prec == "f32":
        return x
    if prec != "fp8":
        raise ValueError(f"unknown precision {prec!r}")
    scale = jax.lax.stop_gradient(jnp.max(jnp.abs(x)) / 448.0 + 1e-30)
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)  # straight through


def _mm(eq, a, b, prec):
    return jnp.einsum(eq, _round(a, prec), _round(b, prec), precision=HI)


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
    q = _mm("bsd,dhk->bshk", x, a["wq"], prec)
    k = _mm("bsd,dhk->bshk", x, a["wk"], prec)
    v = _mm("bsd,dhk->bshk", x, a["wv"], prec)
    if c["attention_bias"]:
        q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
    q, k = _rope(q, c["rope_theta"]), _rope(k, c["rope_theta"])
    h = h + _mm("bshk,hkd->bsd", _attention(q, k, v), a["wo"], prec)
    x = _rms_norm(h, p["ln2"], eps)
    m = p["mlp"]
    g = _mm("bsd,df->bsf", x, m["wg"], prec)
    u = _mm("bsd,df->bsf", x, m["wu"], prec)
    return h + _mm("bsf,fd->bsd", jax.nn.silu(g) * u, m["wd"], prec)


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
        logits = _mm(eq, hb, w, prec)
        gold = jnp.take_along_axis(logits, yb[..., None], -1)[..., 0]
        return jnp.sum(jax.nn.logsumexp(logits, -1) - gold)

    hs = jnp.moveaxis(h.reshape(B, S // lb, lb, D), 1, 0)
    ys = jnp.moveaxis(labels.reshape(B, S // lb, lb), 1, 0)
    return jnp.sum(jax.lax.map(lambda a: block(*a), (hs, ys))) / (B * S)


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


def make_step(c: dict, t: dict, total: int, prec: str = "f32"):
    """``step(params, m, v, count, batch) -> (loss, clipped-gradient leaf
    norms, params, m, v)``: one AdamW step, params and moments donated."""
    b1, b2, eps, wd = t["b1"], t["b2"], t["eps"], t["weight_decay"]

    def step(params, m, v, count, batch):
        value, grads = jax.value_and_grad(loss)(params, batch, c, prec)
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


def run(c: dict, t: dict, total: int, words, role: int, batches,
        shardings=None, prec: str = "f32", steps: int = 3,
        other_m=None, keep_m: bool = False) -> dict:
    """Train ``steps`` steps from the seeded weights on ``batches(k)``.
    Returns the first step's clipped-gradient leaf norms and the leaf norms
    of the change after ``steps`` updates.  With
    ``other_m`` (Adam's first moment after one step of another run, on the
    host), also the leaf norms of the difference of the two first
    gradients; with ``keep_m``, this run's own first moment, on the host."""
    shapes = param_shapes(c)
    init = jax.jit(lambda w: inputs.make_params(w, role, shapes),
                   out_shardings=shardings)
    params = init(words)
    zeros = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p),
                    out_shardings=shardings)
    m, v = zeros(params), zeros(params)
    step = make_step(c, t, total, prec)
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
