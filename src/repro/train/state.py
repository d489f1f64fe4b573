"""TrainState: a plain pytree (dict) + schema/sharding derivation."""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.dist.sharding import param_pspecs, param_shardings
from repro.models.layers import ParamSpec, abstract_params, init_params


def state_schema(api, optimizer) -> Dict[str, Any]:
    return {
        "params": api.schema,
        "opt": optimizer.state_schema(api.schema),
        "step": ParamSpec((), (), init="zeros", dtype="int32"),
    }


def init_state(rng: jax.Array, api, optimizer) -> Dict[str, Any]:
    params = init_params(rng, api.schema)
    return {
        "params": params,
        "opt": optimizer.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def init_sharded_state(seed: int, api, optimizer, shardings) -> Dict[str, Any]:
    """``init_state`` compiled with ``out_shardings``: every shard is created
    on the device that holds it, so a state larger than one device is never
    built whole (nor first on the default device)."""
    init = jax.jit(lambda: init_state(jax.random.PRNGKey(seed), api, optimizer),
                   out_shardings=shardings)
    return init()


def abstract_state(api, optimizer):
    return abstract_params(state_schema(api, optimizer))


def state_pspecs(api, optimizer, rules, mesh, report=None):
    return param_pspecs(state_schema(api, optimizer), rules, mesh, report)


def state_shardings(api, optimizer, rules, mesh, report=None):
    return param_shardings(state_schema(api, optimizer), rules, mesh, report)
