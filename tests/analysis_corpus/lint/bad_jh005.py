"""Corpus: jax.make_mesh without axis_types (Explicit axes under JAX 0.9)."""
import jax
from jax.sharding import AxisType


def meshes(devices):
    a = jax.make_mesh((2, 2), ("data", "model"))                 # flagged
    b = jax.make_mesh((4,), ("data",), devices=devices)         # flagged
    ok = jax.make_mesh((4,), ("data",), axis_types=(AxisType.Auto,))
    return a, b, ok
