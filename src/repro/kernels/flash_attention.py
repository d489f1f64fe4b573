"""Pallas TPU flash attention (blocked online-softmax, causal + GQA).

Target: TPU MXU — block shapes are multiples of 128 on the matmul dims; Q
tile stays resident in VMEM while K/V stream through the innermost grid
dimension; softmax statistics (m, l) and the output accumulator live in VMEM
scratch across K-block iterations.

Grid: (batch·q_heads, n_q_blocks, n_kv_blocks) with the last dim
'arbitrary' (sequential) so the scratch carry is legal.  GQA is expressed in
the K/V index_map (query head h reads kv head h // group).

Validated on CPU via interpret=True against kernels/ref.py (tests/).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *, block_q, block_k, causal,
    sm_scale, window,
):
    i = pl.program_id(1)  # q block
    j = pl.program_id(2)  # kv block
    nk = pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = i * block_q
    k_start = j * block_k

    # skip fully-masked blocks (strictly above the causal diagonal)
    def compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale  # (block_q, d)
        k = k_ref[0].astype(jnp.float32)  # (block_k, d)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (block_q, block_k)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = jnp.ones_like(s, dtype=jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window > 0:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_new
        l_ref[...] = l_new

    if causal:
        # block is live iff some k position <= some q position
        live = k_start <= q_start + block_q - 1
        pl.when(live)(compute)
    else:
        compute()

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"),
)
def flash_attention(
    q: jax.Array,  # (B, Sq, H, d)
    k: jax.Array,  # (B, Skv, KV, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, Sq, H, d = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    G = H // KV
    assert Sq % block_q == 0 and Skv % block_k == 0, (Sq, Skv, block_q, block_k)
    sm_scale = 1.0 / math.sqrt(d)

    qh = jnp.moveaxis(q, 2, 1).reshape(B * H, Sq, d)
    kh = jnp.moveaxis(k, 2, 1).reshape(B * KV, Skv, d)
    vh = jnp.moveaxis(v, 2, 1).reshape(B * KV, Skv, d)

    grid = (B * H, Sq // block_q, Skv // block_k)

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        b, h = bh // H, bh % H
        return (b * KV + h // G, j, 0)

    kernel = functools.partial(
        _flash_kernel,
        block_q=block_q,
        block_k=block_k,
        causal=causal,
        sm_scale=sm_scale,
        window=window,
    )
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), q_map),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    return jnp.moveaxis(out.reshape(B, H, Sq, d), 1, 2)
