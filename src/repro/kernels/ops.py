"""Dispatch wrappers over the Pallas kernels.

``attention`` defers to ``models.attention.attend``, the one dispatcher of
the model paths, which takes the flash kernel where it can run.  ``ssd`` and
``wkv`` run their kernels on TPU backends and, elsewhere (CPU, the dry-run,
CI), the XLA chunked formulations that the kernels mirror
(models/mamba2.ssd_chunked, models/rwkv6.wkv6_chunked).  ``force`` overrides
for tests ('pallas_interpret' runs the kernel body in Python on CPU).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ssd_scan as _ssd
from repro.kernels import wkv6 as _wkv


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def attention(q, k, v, *, causal=True, window=0, force: Optional[str] = None):
    if force == "pallas_interpret":
        return _fa.flash_attention(q, k, v, causal=causal, window=window, interpret=True)
    if force == "pallas":
        return _fa.flash_attention(q, k, v, causal=causal, window=window)
    from repro.models.attention import attend

    return attend(q, k, v, causal=causal, window=window)


def ssd(x, dt, A, Bm, Cm, *, chunk=128, force: Optional[str] = None):
    if force == "pallas_interpret":
        return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk, interpret=True)
    if force == "pallas" or (force is None and _on_tpu()):
        return _ssd.ssd_scan(x, dt, A, Bm, Cm, chunk=chunk)
    from repro.models.mamba2 import ssd_chunked

    y, _ = ssd_chunked(x, dt.astype(jnp.float32), A, Bm, Cm, chunk=chunk)
    return y


def wkv(r, k, v, w, u, *, chunk=64, force: Optional[str] = None):
    if force == "pallas_interpret":
        return _wkv.wkv6(r, k, v, w, u, chunk=chunk, interpret=True)
    if force == "pallas" or (force is None and _on_tpu()):
        return _wkv.wkv6(r, k, v, w, u, chunk=chunk)
    from repro.models.rwkv6 import wkv6_chunked

    o, _ = wkv6_chunked(r, k, v, w, u, chunk=chunk)
    return o
