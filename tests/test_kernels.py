"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret=True)."""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import block_size, flash_attention
from repro.models.mamba2 import ssd_chunked
from repro.models.rwkv6 import wkv6_chunked

TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _tol(dt):
    return TOL[dt]


@pytest.mark.parametrize("S", [128, 256, 1024])
@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("window", [0, 96, "noncausal"])
def test_flash_attention_sweep(S, H, KV, dtype, window):
    """Causal (plain or windowed) or non-causal; at S 1024 the kernel runs
    its 512-position blocks, and skips the masked ones."""
    causal = window != "noncausal"
    window = window if causal else 0
    k0, k1, k2 = jax.random.split(jax.random.PRNGKey(S * H + KV), 3)
    B, d = 2, 64
    q = jax.random.normal(k0, (B, S, H, d), jnp.float32).astype(dtype)
    k = jax.random.normal(k1, (B, S, KV, d), jnp.float32).astype(dtype)
    v = jax.random.normal(k2, (B, S, KV, d), jnp.float32).astype(dtype)
    o_ref = ref.attention_reference(q, k, v, causal=causal, window=window)
    o_pal = ops.attention(q, k, v, causal=causal, window=window,
                          force="pallas_interpret")
    np.testing.assert_allclose(
        np.asarray(o_pal, np.float32), np.asarray(o_ref, np.float32),
        atol=_tol(dtype), rtol=_tol(dtype),
    )


@pytest.mark.parametrize("S,block", [(4096, 512), (1024, 512), (640, 128),
                                     (256, 256), (128, 128), (96, None)])
def test_flash_block_size_follows_the_sequence(S, block):
    """512 wherever it divides the sequence; smaller blocks only for
    sequences that 512 does not divide; none where 128 does not."""
    assert block_size(S) == block


def _grads(attn, q, k, v, w, remat):
    def loss(q, k, v):
        return jnp.sum(attn(q, k, v).astype(jnp.float32) * w)
    if remat:
        loss = jax.checkpoint(loss)
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 1)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("case", ["causal", "window", "noncausal",
                                  "checkpoint"])
def test_flash_attention_grad(H, KV, dtype, case):
    """dq, dk, dv of the kernel's backward (FlashAttention-2: the dq kernel
    and the dk/dv kernel, which sums a kv head over its query heads)
    against autodiff through the reference; 'checkpoint' runs the kernel
    under ``jax.checkpoint``, whose backward runs the forward again."""
    causal, window = case != "noncausal", 96 if case == "window" else 0
    ks = jax.random.split(jax.random.PRNGKey(H * 10 + KV), 4)
    B, S, d = 2, 256, 64
    q = jax.random.normal(ks[0], (B, S, H, d), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, d), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, d), jnp.float32).astype(dtype)
    w = jax.random.normal(ks[3], (B, S, H, d), jnp.float32)
    remat = case == "checkpoint"
    want = _grads(partial(ref.attention_reference, causal=causal,
                          window=window), q, k, v, w, remat)
    got = _grads(partial(flash_attention, causal=causal, window=window,
                         interpret=True), q, k, v, w, remat)
    for name, g, r in zip("qkv", got, want):
        assert g.dtype == r.dtype == dtype, name
        r = np.asarray(r, np.float32)
        np.testing.assert_allclose(
            np.asarray(g, np.float32), r, rtol=_tol(dtype),
            atol=_tol(dtype) * max(1.0, float(np.abs(r).max())),
            err_msg=f"d{name}")


@pytest.mark.parametrize("S,chunk", [(128, 64), (256, 128)])
@pytest.mark.parametrize("P,N", [(32, 16), (64, 64)])
def test_ssd_sweep(S, chunk, P, N):
    ks = jax.random.split(jax.random.PRNGKey(S + P), 5)
    B, H = 2, 3
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.5
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.5
    y_ref = ref.ssd_reference(x, dt, A, Bm, Cm)
    y_pal = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk, force="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               atol=5e-4, rtol=5e-4)
    y_xla = ops.ssd(x, dt, A, Bm, Cm, chunk=chunk)  # CPU jnp path
    np.testing.assert_allclose(np.asarray(y_xla), np.asarray(y_ref),
                               atol=5e-4, rtol=5e-4)


@pytest.mark.parametrize("S,chunk", [(128, 64), (192, 64)])
@pytest.mark.parametrize("K,V", [(32, 32), (64, 64)])
def test_wkv6_sweep(S, chunk, K, V):
    ks = jax.random.split(jax.random.PRNGKey(S + K), 5)
    B, H = 2, 3
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.5
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.5
    v = jax.random.normal(ks[2], (B, S, H, V)) * 0.5
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, K))) * 0.5 + 0.45
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o_ref = ref.wkv6_reference(r, k, v, w, u)
    o_pal = ops.wkv(r, k, v, w, u, chunk=chunk, force="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               atol=5e-4, rtol=5e-4)


def test_chunked_paths_match_sequential_long():
    """Chunk-boundary correctness over many chunks."""
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    B, S, H, P, N = 1, 512, 2, 16, 8
    x = jax.random.normal(ks[0], (B, S, H, P)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.2)
    Bm = jax.random.normal(ks[3], (B, S, N)) * 0.3
    Cm = jax.random.normal(ks[4], (B, S, N)) * 0.3
    y_ref = ref.ssd_reference(x, dt, A, Bm, Cm)
    for chunk in (32, 64, 128):
        y, _ = ssd_chunked(x, dt, A, Bm, Cm, chunk=chunk)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)


def test_wkv_init_state_carried():
    """Chunked WKV with an initial state == sequential on concat sequence."""
    ks = jax.random.split(jax.random.PRNGKey(9), 5)
    B, S, H, K, V = 1, 128, 2, 16, 16
    r = jax.random.normal(ks[0], (B, S, H, K)) * 0.4
    k = jax.random.normal(ks[1], (B, S, H, K)) * 0.4
    v = jax.random.normal(ks[2], (B, S, H, V)) * 0.4
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, S, H, K))) * 0.4 + 0.5
    u = jax.random.normal(ks[4], (H, K)) * 0.3
    o_full = ref.wkv6_reference(r, k, v, w, u)
    half = S // 2
    o1, s1 = wkv6_chunked(r[:, :half], k[:, :half], v[:, :half], w[:, :half], u,
                          chunk=32)
    o2, _ = wkv6_chunked(r[:, half:], k[:, half:], v[:, half:], w[:, half:], u,
                         chunk=32, init_state=s1)
    o_cat = jnp.concatenate([o1, o2], axis=1)
    np.testing.assert_allclose(np.asarray(o_cat), np.asarray(o_full),
                               atol=1e-4, rtol=1e-4)
