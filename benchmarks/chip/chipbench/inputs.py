"""Inputs and weights made from ``--seed``: the one general generator.

Every random stream is keyed by the seed and a path of small integers
(role, kind, step), so the same seed gives the same weights and batches in
the program and in the reference, and no two steps or roles share rows.
Weights and the tenants' batches are drawn on the device (threefry); the
foreground's batches on the host (numpy), where the program's data pipeline
makes them.  Role 0 is the foreground job; role 1 + i is background tenant
i.  Seeds may exceed 32 bits: ``SeedSequence`` folds them into the key.

This module keeps the key stream and no leaf's name: the law of each
seeded weight is the configuration's reference's (its ``init``), which
composes the small named laws at the end of this file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, DATA = 1, 2


def seed_words(seed: int) -> np.ndarray:
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def key(words, *path) -> jax.Array:
    k = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                 impl="threefry2x32")
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def leaf_paths(tree) -> list:
    """``/``-joined key paths of ``tree``'s leaves, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def make_params(model, words, role: int, shapes):
    """Weights of ``role`` with the shapes of ``shapes`` (a pytree of
    ShapeDtypeStructs), traceable: call it under ``jax.jit``.  Each leaf
    has its own key, ``fold_in(key(words, role, WEIGHTS), i)`` for the
    i-th leaf in flattening order, and ``model.init(path, key, shape)``
    (``model`` is the configuration's reference module) draws it by the
    law that the model publishes for the leaf at ``path``."""
    flat, tdef = jax.tree.flatten(shapes)
    base = key(words, role, WEIGHTS)
    out = []
    for i, (path, s) in enumerate(zip(leaf_paths(shapes), flat)):
        leaf = model.init(path, jax.random.fold_in(base, i), tuple(s.shape))
        if leaf.shape != s.shape or leaf.dtype != jnp.float32:
            raise ValueError(f"init({path!r}) gave {leaf.dtype}"
                             f"{list(leaf.shape)}, not float32"
                             f"{list(s.shape)}")
        out.append(leaf.astype(s.dtype))
    return jax.tree.unflatten(tdef, out)


# -- laws that a reference's ``init`` composes ------------------------------

def normal(key, shape, std: float = 1.0) -> jax.Array:
    """N(0, std^2), float32."""
    return jax.random.normal(key, shape, jnp.float32) * std


def uniform(key, shape, lo: float, hi: float) -> jax.Array:
    """U[lo, hi), float32."""
    return jax.random.uniform(key, shape, jnp.float32, lo, hi)


def log_uniform(key, shape, lo: float, hi: float) -> jax.Array:
    """exp(U[log lo, log hi)): uniform in the logarithm, float32."""
    return jnp.exp(uniform(key, shape, math.log(lo), math.log(hi)))


def inverse_softplus(y) -> jax.Array:
    """x with softplus(x) = y, for y > 0."""
    return y + jnp.log(-jnp.expm1(-y))


def fan_in_std(shape, dims) -> float:
    """1/sqrt(fan-in), the fan-in being the product of ``shape``'s
    ``dims``."""
    return 1.0 / math.sqrt(math.prod(shape[d] for d in dims))


def make_tokens(words, role: int, step, batch: int, seq: int, vocab: int,
                power: float):
    """One batch of ``seq`` next-token pairs per row, traceable.  Ids are
    skewed like a unigram law, p(i) ~ i^(1/power - 1): ``floor(u**power *
    vocab)`` for uniform u."""
    u = jax.random.uniform(key(words, role, DATA, step), (batch, seq + 1))
    toks = jnp.minimum(jnp.floor(u ** power * vocab), vocab - 1)
    toks = toks.astype(jnp.int32)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def host_batch(words, role: int, step: int, batch: int, seq: int,
               vocab: int, power: float) -> dict:
    """``make_tokens``'s law on the host, as numpy arrays: the batch that the
    program's data pipeline hands to ``device_put``.  The stream is numpy's,
    keyed by (seed, role, step), so it is not ``make_tokens``'s stream."""
    rng = np.random.default_rng([*map(int, words), role, DATA, step])
    u = rng.random((batch, seq + 1))
    toks = np.minimum(np.floor(u ** power * vocab), vocab - 1).astype(np.int32)
    return {"tokens": np.ascontiguousarray(toks[:, :-1]),
            "labels": np.ascontiguousarray(toks[:, 1:])}
