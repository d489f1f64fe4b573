"""Pallas TPU kernel for the Mamba-2 SSD chunked scan.

One grid step processes one (batch, head, chunk) cell: the intra-chunk
decay-weighted attention-like matmuls run on the MXU, while the inter-chunk
state recurrence is carried in a VMEM scratch accumulator across the
sequential chunk dimension (innermost grid dim, 'arbitrary' semantics).
This is the TPU-native replacement for the paper-era CUDA selective scan:
chunking converts the sequential recurrence into dense matmuls
(DESIGN.md §2, §6).

Inputs are laid out head-major so that every block ends in two dims the
chip can tile: ``(chunk, P)``, ``(chunk, N)``, ``(chunk, 1)`` or
``(1, chunk)`` — a multiple of 8 or the whole dim.  The per-head decay
rates ``A`` sit whole in SMEM and are read as scalars.

Layouts (chunk L, head dim P, state N):
  x  (B, H, nc, L, P)   dt (B, H, nc, L, 1) and (B, H, nc, 1, L)   A (H,)
  Bm (B, nc, L, N)      Cm (B, nc, L, N)
  y  (B, H, nc, L, P)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(a_ref, x_ref, dtc_ref, dtr_ref, b_ref, c_ref, y_ref, state_ref):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    A = a_ref[pl.program_id(1)]  # scalar decay rate for this head
    dt = dtc_ref[...].astype(jnp.float32)  # (L, 1)
    x = x_ref[...].astype(jnp.float32)  # (L, P)
    Bm = b_ref[...].astype(jnp.float32)  # (L, N)
    Cm = c_ref[...].astype(jnp.float32)  # (L, N)

    # inclusive in-chunk cumulative log-decay, as a column and as a row
    # (masked sums over the lower triangle: the chip lowers no cumsum)
    chunk = x.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col
    dA = dt * A  # (L, 1)
    dA_row = dtr_ref[...].astype(jnp.float32) * A  # (1, L)
    cum = jnp.sum(jnp.where(mask, dA_row, 0.0), axis=1, keepdims=True)  # (L, 1)
    cum_row = jnp.sum(jnp.where(row <= col, dA, 0.0), axis=0, keepdims=True)
    xb = x * dt

    # intra-chunk: Y = (C B^T ⊙ L) X̄ ; L[i,j] = exp(cum_i - cum_j), j <= i
    L = jnp.where(mask, jnp.exp(cum - cum_row), 0.0)
    CB = jax.lax.dot_general(
        Cm, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )
    y = jax.lax.dot_general(
        CB * L, xb, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )

    # off-diagonal: Y += exp(cum) ⊙ (C · state)   (state: (N, P))
    y = y + jnp.exp(cum) * jax.lax.dot_general(
        Cm, state_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    y_ref[...] = y.astype(y_ref.dtype)

    # state update: S' = exp(cum_L) S + (B ⊙ exp(cum_L - cum))^T X̄
    cum_end = cum[chunk - 1:, :]  # (1, 1)
    state_ref[...] = state_ref[...] * jnp.exp(cum_end) + jax.lax.dot_general(
        Bm * jnp.exp(cum_end - cum), xb, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(
    x: jax.Array,  # (B, S, H, P)
    dt: jax.Array,  # (B, S, H) fp32 step sizes
    A: jax.Array,  # (H,) negative decay rates
    Bm: jax.Array,  # (B, S, N)
    Cm: jax.Array,  # (B, S, N)
    *,
    chunk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    xr = jnp.moveaxis(x.reshape(B, nc, chunk, H, P), 3, 1)  # (B, H, nc, L, P)
    dth = jnp.moveaxis(dt.reshape(B, nc, chunk, H), 3, 1)  # (B, H, nc, L)
    br = Bm.reshape(B, nc, chunk, N)
    cr = Cm.reshape(B, nc, chunk, N)

    head_block = lambda last: pl.BlockSpec(  # noqa: E731
        (None, None, None) + last, lambda b, h, c: (b, h, c, 0, 0))
    chunk_block = pl.BlockSpec((None, None, chunk, N), lambda b, h, c: (b, c, 0, 0))
    out = pl.pallas_call(
        _ssd_kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            head_block((chunk, P)),
            head_block((chunk, 1)),
            head_block((1, chunk)),
            chunk_block,
            chunk_block,
        ],
        out_specs=head_block((chunk, P)),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, chunk, P), x.dtype),
        scratch_shapes=[pltpu.VMEM((N, P), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(A.astype(jnp.float32), xr, dth[..., None], dth[..., None, :], br, cr)
    return jnp.moveaxis(out, 1, 3).reshape(B, S, H, P)
