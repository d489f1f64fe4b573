"""Pallas TPU kernel for the RWKV-6 WKV recurrence (chunked parallel form).

Same chunking structure as the SSD kernel: per-(batch, head) the sequential
chunk dimension is the innermost grid axis; the state matrix is a VMEM
scratch carried across chunks, held transposed as (V, K) so that the
per-key decay scales it along its last dim; intra-chunk work is dense MXU
matmuls with per-channel data-dependent decays.

Inputs are laid out head-major so that every block ends in ``(chunk, K)``,
``(chunk, V)`` or ``(1, K)``: a multiple of 8 or the whole dim, which the
chip can tile.

Layouts (chunk L, key dim K, value dim V):
  r/k/w (B, H, nc, L, K)   v (B, H, nc, L, V)   u (H, 1, K)
  o     (B, H, nc, L, V)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _wkv6_kernel(u_ref, r_ref, k_ref, v_ref, w_ref, o_ref, state_ref):
    c_idx = pl.program_id(2)

    @pl.when(c_idx == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    r = r_ref[...].astype(jnp.float32)  # (L, K)
    k = k_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)  # (L, V)
    w = w_ref[...].astype(jnp.float32)  # (L, K) decays in (0,1)
    u = u_ref[...].astype(jnp.float32)  # (1, K)

    chunk = r.shape[0]
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    lw = jnp.log(jnp.clip(w, 1e-6, 1.0))
    # inclusive cumsum over the chunk as a lower-triangular matmul (the
    # chip lowers no cumsum)
    cs = jax.lax.dot_general(
        (row >= col).astype(jnp.float32), lw, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )  # (L, K)

    r_dec = r * jnp.exp(cs - lw)  # r_t ⊙ exp(cs_{t-1})
    k_dec = k * jnp.exp(-cs)  # k_j ⊙ exp(-cs_j)

    A = jax.lax.dot_general(
        r_dec, k_dec, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )  # (L, L)
    A = jnp.where(row > col, A, 0.0)
    diag = jnp.sum(r * u * k, axis=1, keepdims=True)  # (L, 1)

    o = jax.lax.dot_general(
        A, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    o = o + diag * v
    # inter-chunk: o += (r ⊙ exp(cs_{t-1})) · state   (state^T: (V, K))
    o = o + jax.lax.dot_general(
        r_dec, state_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    o_ref[...] = o.astype(o_ref.dtype)

    # state' = diag(exp(cs_L)) state + (k ⊙ exp(cs_L - cs))^T v, transposed
    cs_end = cs[chunk - 1:, :]  # (1, K)
    k_tail = k * jnp.exp(cs_end - cs)
    state_ref[...] = state_ref[...] * jnp.exp(cs_end) + jax.lax.dot_general(
        v, k_tail, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6(
    r: jax.Array,  # (B, S, H, K)
    k: jax.Array,
    v: jax.Array,  # (B, S, H, V)
    w: jax.Array,  # (B, S, H, K) decays in (0,1)
    u: jax.Array,  # (H, K)
    *,
    chunk: int = 64,
    interpret: bool = False,
) -> jax.Array:
    B, S, H, K = r.shape
    V = v.shape[-1]
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk

    def head_major(a):
        return jnp.moveaxis(a.reshape(B, nc, chunk, H, a.shape[-1]), 3, 1)

    io_spec = lambda last: pl.BlockSpec(  # noqa: E731
        (None, None, None, chunk, last), lambda b, h, c: (b, h, c, 0, 0))
    out = pl.pallas_call(
        _wkv6_kernel,
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((None, 1, K), lambda b, h, c: (h, 0, 0)),
            io_spec(K),
            io_spec(K),
            io_spec(V),
            io_spec(K),
        ],
        out_specs=io_spec(V),
        out_shape=jax.ShapeDtypeStruct((B, H, nc, chunk, V), r.dtype),
        scratch_shapes=[pltpu.VMEM((V, K), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(u.reshape(H, 1, K), head_major(r), head_major(k), head_major(v),
      head_major(w))
    return jnp.moveaxis(out, 1, 3).reshape(B, S, H, V)
