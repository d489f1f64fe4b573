"""Inputs and weights made from ``--seed``: the one general generator.

Every random stream is keyed by the seed and a path of small integers
(role, kind, step), so the same seed gives the same weights and batches in
the program and in the reference, and no two steps or roles share rows.
Weights and the tenants' batches are drawn on the device (threefry); the
foreground's batches on the host (numpy), where the program's data pipeline
makes them.  Role 0 is the foreground job; role 1 + i is background tenant
i.  Seeds may exceed 32 bits: ``SeedSequence`` folds them into the key.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

WEIGHTS, DATA = 1, 2

# Initial std of each leaf, by its key.  Matrices take 1/sqrt(fan-in), where
# the fan-in is the product of the listed dims of the stacked (layers-first)
# leaf; the rest take a fixed std.  Norm scales are offsets from 1 and
# biases are non-zero, so that both take part in the comparison.
FAN_IN_DIMS = {"wq": (1,), "wk": (1,), "wv": (1,), "wo": (1, 2),
               "wg": (1,), "wu": (1,), "wd": (1,)}
FIXED_STD = {"embed": 0.02, "lm_head": 0.02, "ln1": 0.02, "ln2": 0.02,
             "final_norm": 0.02, "bq": 0.02, "bk": 0.02, "bv": 0.02}


def seed_words(seed: int) -> np.ndarray:
    return np.random.SeedSequence(int(seed)).generate_state(2, np.uint32)


def key(words, *path) -> jax.Array:
    k = jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                 impl="threefry2x32")
    for p in path:
        k = jax.random.fold_in(k, p)
    return k


def leaf_std(name: str, shape: tuple) -> float:
    if name in FIXED_STD:
        return FIXED_STD[name]
    if name not in FAN_IN_DIMS:
        raise KeyError(f"no initial std for a leaf named {name!r}")
    return 1.0 / math.sqrt(math.prod(shape[d] for d in FAN_IN_DIMS[name]))


def leaf_paths(tree) -> list:
    """``/``-joined key paths of ``tree``'s leaves, in flattening order."""
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(getattr(k, "key", k)) for k in path)
            for path, _ in flat]


def make_params(words, role: int, shapes):
    """Weights of ``role`` with the shapes of ``shapes`` (a pytree of
    ShapeDtypeStructs), float32, traceable: call it under ``jax.jit``."""
    flat, tdef = jax.tree.flatten(shapes)
    base = key(words, role, WEIGHTS)
    out = []
    for i, (path, s) in enumerate(zip(leaf_paths(shapes), flat)):
        std = leaf_std(path.rsplit("/", 1)[-1], tuple(s.shape))
        leaf = jax.random.normal(jax.random.fold_in(base, i), s.shape,
                                 jnp.float32) * std
        out.append(leaf.astype(s.dtype))
    return jax.tree.unflatten(tdef, out)


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
