"""train_step / eval_step factories with pjit shardings.

``make_train_step`` builds the full step: fwd + bwd + gradient clipping +
optimizer update (+ optional PowerSGD gradient compression and burst-plan
activation constraints).  ``jit_train_step`` closes it over mesh shardings —
this is exactly what launch/dryrun.py lowers.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, ShapeConfig
from repro.dist import fsdp
from repro.dist.sharding import (
    batch_pspecs,
    param_pspecs,
    sharding_rules,
)
from repro.train.state import state_pspecs, state_schema


def make_train_step(api, optimizer, grad_transform: Optional[Callable] = None):
    def train_step(state, batch):
        (loss, metrics), grads = jax.value_and_grad(api.loss, has_aux=True)(
            state["params"], batch
        )
        if grad_transform is not None:
            grads = grad_transform(grads)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, state["opt"],
                                                   state["params"])
        new_state = {"params": new_params, "opt": new_opt, "step": state["step"] + 1}
        return new_state, metrics

    return train_step


def make_forward(api):
    """Full-sequence forward (prefill benchmark shape)."""

    def fwd(params, batch):
        if "frames" in batch:
            return api.forward(params, batch["frames"], batch["tokens"])
        if "patch_embeds" in batch:
            return api.forward(params, batch["tokens"], patch_embeds=batch["patch_embeds"])
        return api.forward(params, batch["tokens"])

    return fwd


def make_decode_step(api):
    def step(params, batch):
        return api.decode_step(params, batch["token"], batch["cache"], batch["cache_len"])

    return step


def jit_train_step(api, optimizer, mesh, shape: ShapeConfig,
                   rules: Optional[dict] = None, report=None,
                   name: str = "step_with_fsdp"):
    """Returns (jitted_fn, state_shardings, batch_shardings); the state
    argument is donated to the step's output.  The compiled program is
    named ``jit_<name>`` (the background tenants' ``step_with_fsdp_bg``)."""
    from repro.models.api import input_specs

    cfg = api.cfg
    rules = rules or sharding_rules(cfg, mesh, shape)
    st_specs = state_pspecs(api, optimizer, rules, mesh, report)
    bt_specs = batch_pspecs(cfg, shape, rules, mesh, input_specs(cfg, shape), report)
    st_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), st_specs,
                         is_leaf=lambda x: isinstance(x, P))
    bt_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), bt_specs,
                         is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(api, optimizer)

    def step_with_fsdp(state, batch):
        with fsdp.context(mesh, rules):
            return step(state, batch)

    step_with_fsdp.__name__ = step_with_fsdp.__qualname__ = name
    fn = jax.jit(
        step_with_fsdp,
        in_shardings=(st_sh, bt_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,),
    )
    return fn, st_sh, bt_sh


def bg_step_factory(cfg: ModelConfig, *, batch: int = 4, seq: int = 8,
                    seed: int = 0, on_loss: Optional[Callable] = None,
                    per_device_batch: Optional[int] = None):
    """``make_bg_step_fn`` for executable gap collocation
    (``Collocator.run_executable``): returns a callable that, given a gap
    submesh, jits a REAL ``cfg`` training step onto it with a private state
    replica and dispatches one step per call.  The state and the batch are
    created sharded on the submesh, and the step is compiled when the
    callable is built, not on any of its calls.  ``on_loss`` observes each
    step's (device-resident) loss.  Shared by bench_collocation,
    multiplex_demo and the training entrypoint's --bg-arch path.

    ``per_device_batch`` sizes the tenant's step to its own chunk width
    (the per-tenant bg step quantum): each jitted step uses
    ``per_device_batch * mesh.devices.size`` samples, so a tenant holding a
    wide gap chunk trains a proportionally bigger global batch instead of
    everyone running the batch sized for the global gap minimum.  Without
    it, ``batch`` is the fixed global batch.

    The returned factory carries a ``signature`` attribute
    (``"{name}-L{layers}-b{batch}-s{seq}-r{seed}"``, or ``-pdb{n}-`` in
    per-device-batch mode) identifying the compiled executable for
    ``ExecutableCache`` reuse across re-plans: two tenants built from
    factories with equal signatures and landing on the same gap submesh
    share one jitted step.  (The cache key also carries the submesh device
    ids/shape, so per-device sizing never aliases across chunk widths.)
    """
    import dataclasses

    from repro.configs import TRAIN_4K
    from repro.models.api import get_model, make_batch
    from repro.optim.optimizer import make_optimizer
    from repro.train.state import init_sharded_state

    api = get_model(cfg)
    opt = make_optimizer(cfg)

    def make_bg_step_fn(mesh):
        b_global = batch
        if per_device_batch is not None:
            b_global = max(1, per_device_batch * int(mesh.devices.size))
        shape = dataclasses.replace(TRAIN_4K, seq_len=seq,
                                    global_batch=b_global, name="bg")
        fn, st_sh, bt_sh = jit_train_step(api, opt, mesh, shape,
                                          name="step_with_fsdp_bg")
        holder = {"state": init_sharded_state(seed, api, opt, st_sh)}
        make = jax.jit(
            lambda: make_batch(jax.random.PRNGKey(seed + 1), cfg, b_global, seq),
            out_shardings=bt_sh,
        )
        b = make()
        # compile at set-up and call the executable itself: a jitted call
        # would trace again under the caller's mesh context (the fg loop's
        # ``with mesh``) and compile a second time inside a timed step
        compiled = fn.lower(holder["state"], b).compile()

        def step():
            holder["state"], metrics = compiled(holder["state"], b)
            if on_loss is not None:
                on_loss(metrics["loss"])
            return metrics["loss"]

        return step

    tag = (f"pdb{per_device_batch}" if per_device_batch is not None
           else f"b{batch}")
    make_bg_step_fn.signature = (
        f"{cfg.name}-L{cfg.num_layers}-{tag}-s{seq}-r{seed}")
    return make_bg_step_fn


def jit_forward(api, mesh, shape: ShapeConfig, rules: Optional[dict] = None, report=None):
    from repro.dist.sharding import param_shardings
    from repro.models.api import input_specs

    cfg = api.cfg
    rules = rules or sharding_rules(cfg, mesh, shape)
    p_sh = param_shardings(api.schema, rules, mesh, report)
    specs = input_specs(cfg, shape)
    bt_specs = batch_pspecs(cfg, shape, rules, mesh, specs, report)
    bt_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), bt_specs,
                         is_leaf=lambda x: isinstance(x, P))
    fwd = make_forward(api)

    def fwd_with_fsdp(params, batch):
        with fsdp.context(mesh, rules):
            return fwd(params, batch)

    fn = jax.jit(fwd_with_fsdp, in_shardings=(p_sh, bt_sh))
    return fn, p_sh, bt_sh


def jit_decode_step(api, mesh, shape: ShapeConfig, rules: Optional[dict] = None,
                    donate: bool = True, report=None):
    from repro.dist.sharding import param_shardings
    from repro.models.api import input_specs

    cfg = api.cfg
    rules = rules or sharding_rules(cfg, mesh, shape)
    p_sh = param_shardings(api.schema, rules, mesh, report)
    specs = input_specs(cfg, shape)
    bt_specs = batch_pspecs(cfg, shape, rules, mesh, specs, report)
    bt_sh = jax.tree.map(lambda p: NamedSharding(mesh, p), bt_specs,
                         is_leaf=lambda x: isinstance(x, P))

    def step(params, batch):
        with fsdp.context(mesh, rules):
            return api.decode_step(params, batch["token"], batch["cache"], batch["cache_len"])

    fn = jax.jit(
        step,
        in_shardings=(p_sh, bt_sh),
        out_shardings=(None, bt_sh["cache"]),
        donate_argnums=() if not donate else (1,),
    )
    return fn, p_sh, bt_sh
