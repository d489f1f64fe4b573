"""Pallas TPU flash attention, forward and backward (causal, window, GQA).

The forward streams K/V blocks past a resident Q block with an online
softmax; the scores, their statistics and the output accumulator stay in
VMEM, and it returns each row's log-sum-exp (f32) beside the output.  The
backward is FlashAttention-2's: ``D = rowsum(dO * O)`` in XLA, then a dq
kernel that streams K/V blocks past a Q block, and a dk/dv kernel that
streams Q blocks (of every query head of the group, GQA) past a K/V block;
both recompute ``p`` from q, k and the log-sum-exp.  ``flash_attention``
is a ``jax.custom_vjp``, so it trains, and under ``jax.checkpoint`` its
forward runs again in the backward pass.

Operands enter the MXU dots in the input dtype (q, k, v, and ``p`` and
``dS`` cast to it) with f32 accumulation; softmax statistics and
accumulators are f32.  Blocks wholly outside the causal triangle or the
sliding window are skipped twice: no compute (``pl.when``), and no copy, as
the K/V (or Q) ``index_map`` clamps a dead step to the nearest live block,
which Pallas does not fetch again.  Block sizes follow the sequence
(``block_size``).

Arrays run head-major, (batch·heads, seq, d); GQA is expressed in the
index maps (query head h reads kv head h // group).  Validated on CPU with
``interpret=True`` against kernels/ref.py (tests/).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30
BLOCKS = (512, 256, 128)  # q and kv block sizes, tried largest first
LANES = 128
_QK = (((1,), (1,)), ((), ()))  # a @ b.T
_AB = (((1,), (0,)), ((), ()))  # a @ b


def block_size(seq: int) -> Optional[int]:
    """The q and kv block for ``seq`` positions: the largest of ``BLOCKS``
    that divides it, or None when none does (the kernel cannot run)."""
    return next((b for b in BLOCKS if seq % b == 0), None)


class _Cfg(NamedTuple):
    causal: bool
    window: int
    block_q: int
    block_k: int
    heads: int  # query heads
    groups: int  # query heads per kv head
    interpret: bool


# -- which blocks hold an unmasked entry --------------------------------------
def _kv_range(i, c: _Cfg, nk: int):
    """First and last kv block that q block ``i`` attends to."""
    lo = 0
    if c.window > 0:
        lo = jnp.maximum((i * c.block_q - c.window + 1) // c.block_k, 0)
    hi = nk - 1
    if c.causal:
        hi = jnp.minimum((i * c.block_q + c.block_q - 1) // c.block_k, hi)
    return lo, hi


def _q_range(j, c: _Cfg, nq: int):
    """First and last q block that attends to kv block ``j``."""
    lo = (j * c.block_k) // c.block_q if c.causal else 0
    hi = nq - 1
    if c.window > 0:
        hi = jnp.minimum((j * c.block_k + c.block_k + c.window - 2) // c.block_q, hi)
    return lo, hi


def _mask(q0, k0, shape, c: _Cfg, q_axis: int):
    """Unmasked entries of a block whose q positions start at ``q0`` along
    axis ``q_axis`` and whose kv positions start at ``k0`` along the other."""
    qp = q0 + jax.lax.broadcasted_iota(jnp.int32, shape, q_axis)
    kp = k0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - q_axis)
    ok = kp <= qp if c.causal else jnp.ones(shape, jnp.bool_)
    if c.window > 0:
        ok &= kp > qp - c.window
    return ok


def _when_live(live, q0, k0, c: _Cfg, body):
    """Run ``body(masked)`` where ``live``: with ``masked`` False on blocks
    wholly inside the unmasked region, so most blocks skip the mask."""
    if not (c.causal or c.window > 0):
        pl.when(live)(lambda: body(False))
        return
    full = True
    if c.causal:
        full = k0 + c.block_k - 1 <= q0
    if c.window > 0:
        full = jnp.logical_and(full, k0 > q0 + c.block_q - 1 - c.window)
    pl.when(jnp.logical_and(live, full))(lambda: body(False))
    pl.when(jnp.logical_and(live, jnp.logical_not(full)))(lambda: body(True))


def _scaled_q(q_ref, sm_scale):
    return (q_ref[0].astype(jnp.float32) * sm_scale).astype(q_ref.dtype)


def _lanes(x, n: int):
    """A (rows, 128) lane-replicated row statistic widened or cut to ``n``
    columns."""
    return jnp.tile(x, (1, -(-n // LANES)))[:, :n]


# -- kernels ------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                c: _Cfg, sm_scale: float):
    # the row statistics m and l are kept across all 128 lanes, (bq, 128):
    # they then broadcast over a block's columns without a relayout
    i, j, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q0, k0 = i * c.block_q, j * c.block_k

    def step(masked):
        s = jax.lax.dot_general(_scaled_q(q_ref, sm_scale), k_ref[0], _QK,
                                preferred_element_type=jnp.float32)
        if masked:
            s = jnp.where(_mask(q0, k0, s.shape, c, 0), s, NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        p = jnp.exp(s - _lanes(m_new, c.block_k))
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)[:, None]
        acc_ref[...] = acc_ref[...] * _lanes(alpha, acc_ref.shape[1]) + \
            jax.lax.dot_general(p.astype(v_ref.dtype), v_ref[0], _AB,
                                preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    lo, hi = _kv_range(i, c, nk)
    _when_live(jnp.logical_and(j >= lo, j <= hi), q0, k0, c, step)

    @pl.when(j == nk - 1)
    def _finalize():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = (acc_ref[...] / _lanes(l, acc_ref.shape[1])).astype(o_ref.dtype)
        lse_ref[0] = (m_ref[...] + jnp.log(l))[:, 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, acc_ref,
               *, c: _Cfg, sm_scale: float):
    i, j, nk = pl.program_id(1), pl.program_id(2), pl.num_programs(2)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q0, k0 = i * c.block_q, j * c.block_k

    def step(masked):
        dt = k_ref.dtype
        s = jax.lax.dot_general(_scaled_q(q_ref, sm_scale), k_ref[0], _QK,
                                preferred_element_type=jnp.float32)
        p = jnp.exp(s - lse_ref[0][:, None])
        if masked:
            p = jnp.where(_mask(q0, k0, s.shape, c, 0), p, 0.0)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _QK,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_ref[0][:, None])
        acc_ref[...] += jax.lax.dot_general(ds.astype(dt), k_ref[0], _AB,
                                            preferred_element_type=jnp.float32)

    lo, hi = _kv_range(i, c, nk)
    _when_live(jnp.logical_and(j >= lo, j <= hi), q0, k0, c, step)

    @pl.when(j == nk - 1)
    def _finalize():
        dq_ref[0] = (acc_ref[...] * sm_scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, c: _Cfg, sm_scale: float, nq: int):
    # the scores run transposed, (kv, q): the q rows' log-sum-exp and D
    # then broadcast along the lanes, and no block needs a transpose
    j, t, nt = pl.program_id(1), pl.program_id(2), pl.num_programs(2)
    i = t % nq  # t walks (query head of the group, q block)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q0, k0 = i * c.block_q, j * c.block_k

    def step(masked):
        dt = q_ref.dtype
        q = _scaled_q(q_ref, sm_scale)
        st = jax.lax.dot_general(k_ref[0], q, _QK,
                                 preferred_element_type=jnp.float32)
        pt = jnp.exp(st - lse_ref[...])
        if masked:
            pt = jnp.where(_mask(q0, k0, st.shape, c, 1), pt, 0.0)
        dv_acc[...] += jax.lax.dot_general(pt.astype(dt), do_ref[0], _AB,
                                           preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do_ref[0], _QK,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[...])
        dk_acc[...] += jax.lax.dot_general(dst.astype(dt), q, _AB,
                                           preferred_element_type=jnp.float32)

    lo, hi = _q_range(j, c, nq)
    _when_live(jnp.logical_and(i >= lo, i <= hi), q0, k0, c, step)

    @pl.when(t == nt - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# -- calls --------------------------------------------------------------------
def _call(kernel, grid, in_specs, out_specs, out_shape, scratch, name, c):
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name=name,
        interpret=c.interpret,
    )


def _q_major_specs(c: _Cfg, nq: int, nk: int, d: int):
    """Block specs over the grid (b·h, q block, kv block) of the forward
    and dq kernels: a q-side block, a kv block (a dead step is clamped to
    a live block, so it fetches nothing new), and a q block's row vector."""
    H, G = c.heads, c.groups

    def q_map(bh, i, j):
        return (bh, i, 0)

    def kv_map(bh, i, j):
        lo, hi = _kv_range(i, c, nk)
        b, h = bh // H, bh % H
        return (b * (H // G) + h // G, jnp.clip(j, lo, hi), 0)

    def row_map(bh, i, j):
        return (bh, 0, i)

    return (pl.BlockSpec((1, c.block_q, d), q_map),
            pl.BlockSpec((1, c.block_k, d), kv_map),
            pl.BlockSpec((None, 1, c.block_q), row_map))


def _forward(qh, kh, vh, c: _Cfg):
    BH, Sq, d = qh.shape
    nq, nk = Sq // c.block_q, kh.shape[1] // c.block_k
    q_spec, kv_spec, row_spec = _q_major_specs(c, nq, nk, d)
    return _call(
        functools.partial(_fwd_kernel, c=c, sm_scale=1.0 / math.sqrt(d)),
        (BH, nq, nk), [q_spec, kv_spec, kv_spec], [q_spec, row_spec],
        [jax.ShapeDtypeStruct(qh.shape, qh.dtype),
         jax.ShapeDtypeStruct((BH, 1, Sq), jnp.float32)],
        [pltpu.VMEM((c.block_q, d), jnp.float32),
         pltpu.VMEM((c.block_q, LANES), jnp.float32),
         pltpu.VMEM((c.block_q, LANES), jnp.float32)],
        "flash_attention_fwd", c,
    )(qh, kh, vh)


def _backward_dq(qh, kh, vh, do, lse, di, c: _Cfg):
    BH, Sq, d = qh.shape
    nq, nk = Sq // c.block_q, kh.shape[1] // c.block_k
    q_spec, kv_spec, row_spec = _q_major_specs(c, nq, nk, d)
    return _call(
        functools.partial(_dq_kernel, c=c, sm_scale=1.0 / math.sqrt(d)),
        (BH, nq, nk), [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        q_spec, jax.ShapeDtypeStruct(qh.shape, qh.dtype),
        [pltpu.VMEM((c.block_q, d), jnp.float32)],
        "flash_attention_bwd_dq", c,
    )(qh, kh, vh, do, lse, di)


def _backward_dkv(qh, kh, vh, do, lse, di, c: _Cfg):
    BH, Sq, d = qh.shape
    BKV, Skv, _ = kh.shape
    H, G = c.heads, c.groups
    nq, nk = Sq // c.block_q, Skv // c.block_k

    def q_block(bkv, j, t):
        lo, hi = _q_range(j, c, nq)
        b, kvh = bkv // (H // G), bkv % (H // G)
        return b * H + kvh * G + t // nq, jnp.clip(t % nq, lo, hi)

    def q_map(bkv, j, t):
        return (*q_block(bkv, j, t), 0)

    def row_map(bkv, j, t):
        bh, i = q_block(bkv, j, t)
        return (bh, 0, i)

    def kv_map(bkv, j, t):
        return (bkv, j, 0)

    q_spec = pl.BlockSpec((1, c.block_q, d), q_map)
    kv_spec = pl.BlockSpec((1, c.block_k, d), kv_map)
    row_spec = pl.BlockSpec((None, 1, c.block_q), row_map)
    return _call(
        functools.partial(_dkv_kernel, c=c, sm_scale=1.0 / math.sqrt(d), nq=nq),
        (BKV, nk, G * nq), [q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        [kv_spec, kv_spec],
        [jax.ShapeDtypeStruct(kh.shape, kh.dtype),
         jax.ShapeDtypeStruct(vh.shape, vh.dtype)],
        [pltpu.VMEM((c.block_k, d), jnp.float32),
         pltpu.VMEM((c.block_k, d), jnp.float32)],
        "flash_attention_bwd_dkv", c,
    )(qh, kh, vh, do, lse, di)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _flash(qh, kh, vh, c: _Cfg):
    return _forward(qh, kh, vh, c)[0]


def _flash_fwd(qh, kh, vh, c: _Cfg):
    o, lse = _forward(qh, kh, vh, c)
    return o, (qh, kh, vh, o, lse)


def _flash_bwd(c: _Cfg, res, do):
    qh, kh, vh, o, lse = res
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    di = di[:, None, :]
    dq = _backward_dq(qh, kh, vh, do, lse, di, c)
    dk, dv = _backward_dkv(qh, kh, vh, do, lse, di, c)
    return dq, dk, dv


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "interpret"))
def flash_attention(
    q: jax.Array,  # (B, Sq, H, d)
    k: jax.Array,  # (B, Skv, KV, d)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """Attention of ``q`` over ``k``/``v``, differentiable.  Each sequence
    length must have a ``block_size``."""
    B, Sq, H, d = q.shape
    Skv, KV = k.shape[1], k.shape[2]
    bq, bk = block_size(Sq), block_size(Skv)
    if bq is None or bk is None:
        raise ValueError(f"no block of {BLOCKS} divides {Sq} and {Skv}")
    c = _Cfg(causal, window, bq, bk, H, H // KV, interpret)

    def heads_major(x):
        return jnp.moveaxis(x, 2, 1).reshape(-1, x.shape[1], d)

    o = _flash(heads_major(q), heads_major(k), heads_major(v), c)
    return jnp.moveaxis(o.reshape(B, H, Sq, d), 1, 2)
