"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
importing this module never touches jax device state.  The dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
smoke tests and benches see the real (1-device) platform.

Every builder here gives every axis ``AxisType.Auto``: the models place
activations with ``with_sharding_constraint`` (``dist/fsdp.py``), which only
accepts Auto axes, while ``jax.make_mesh`` defaults to Explicit ones.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.core.plan import normalize_quanta, pack_ranges, pow2_floor, serving_plan


def _auto(n: int) -> Tuple[AxisType, ...]:
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_mesh(data: int, model: int, pod: int = 1, devices: Optional[Sequence] = None):
    """Arbitrary mesh (tests / small-scale demos on host devices).

    ``devices`` restricts the mesh to an explicit device subset (elastic
    re-mesh over survivors, submesh demos); default is the process devices.
    """
    if pod > 1:
        return jax.make_mesh((pod, data, model), ("pod", "data", "model"),
                             devices=devices, axis_types=_auto(3))
    return jax.make_mesh((data, model), ("data", "model"), devices=devices,
                         axis_types=_auto(2))


def pow2_mesh_shape(n_devices: int) -> Tuple[int, int]:
    """The (data, model) shape ``largest_pow2_mesh`` would build — pure
    arithmetic, no jax device state, so the static sharding sweep
    (``repro.analysis.shardcheck``) can enumerate every mesh shape reachable
    after a failure without constructing a single device."""
    if n_devices < 1:
        raise ValueError(f"need at least one device, got {n_devices}")
    cap = 1
    while cap * cap * 4 <= pow2_floor(n_devices):
        cap *= 2
    candidates = []
    m = 1
    while m <= cap:
        candidates.append(m)
        m *= 2
    # widest model axis within the 4:1 bound that maximizes device coverage
    model = max(candidates, key=lambda m: (n_devices // m * m, m))
    return n_devices // model, model


def largest_pow2_mesh(n_devices: int, devices: Optional[Sequence] = None):
    """Elastic re-mesh: the largest (data, model) mesh that fits n_devices,
    favoring the data axis 4:1 (used after failures).  The model axis stays
    a power of two — sharding rules genuinely need it to divide head/hidden
    dims — but the data axis is just a batch split, so a non-power-of-two
    survivor count keeps every device the model width allows (7 survivors
    -> 7x1, not 4x1; the planner's scale set covers non-pow2 pools too).
    Only a sub-``model`` remainder is ever left out of the mesh, and only
    when a narrower model axis would not cover more devices."""
    data, model = pow2_mesh_shape(n_devices)
    if devices is not None:
        devices = list(devices)[: data * model]
    return make_mesh(data, model, devices=devices)


def mesh_axis_sizes(mesh: Mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def remesh_for_pool(device_ids, *, devices: Optional[Sequence] = None) -> Mesh:
    """Re-carve a mesh onto a surviving device pool (applied reconfig).

    ``device_ids`` is the healthy pool from a reconfiguration event
    (``CoordinatorLoop`` publishes the coordinator's sorted healthy set).
    Ids map positionally onto the process device list — the same
    positional contract ``submesh_from_range`` and the executable-cache
    eviction use — and ids beyond it (devices hosted by other processes,
    or virtual ids above the local pool) are skipped: each host re-carves
    over *its* survivors.  The carving itself is ``largest_pow2_mesh``, so
    a non-pow2 survivor count keeps every device the model width allows.
    """
    devs = list(devices) if devices is not None else jax.devices()
    local = [devs[int(i)] for i in device_ids if 0 <= int(i) < len(devs)]
    if not local:
        raise ValueError(
            f"reconfig pool {sorted(int(i) for i in device_ids)} has no "
            f"local devices (process has {len(devs)})"
        )
    return largest_pow2_mesh(len(local), devices=local)


# ---------------------------------------------------------------------------
# Plan-driven submeshes (executable gap collocation — paper §5, TPU mode)
# ---------------------------------------------------------------------------


def submesh_from_range(start: int, end: int, *, model: int = 1,
                       devices: Optional[Sequence] = None) -> Mesh:
    """A (data, model) Mesh over the device-index range [start, end).

    Devices are taken positionally from ``devices`` (default: the process
    device list), so two non-overlapping index ranges always yield disjoint
    submeshes — the invariant the collocator relies on.
    """
    devs = list(devices) if devices is not None else jax.devices()
    n = end - start
    if n <= 0:
        raise ValueError(f"empty device range [{start}, {end})")
    if start < 0 or end > len(devs):
        raise ValueError(
            f"device range [{start}, {end}) outside the {len(devs)}-device set"
        )
    if n % model:
        raise ValueError(f"range size {n} not divisible by model={model}")
    arr = np.array(devs[start:end], dtype=object).reshape(n // model, model)
    return Mesh(arr, ("data", "model"), axis_types=_auto(2))


@dataclass(frozen=True)
class PlanSubmeshes:
    """Disjoint fg/bg submeshes for one BurstPlan.

    ``fg_range``/``fg_mesh`` span the plan's peak foreground device usage;
    ``bg`` maps each gap stage to the largest free device range (after
    excluding parallel-branch placements active in that stage) and its Mesh.
    ``bg_tenants`` maps each gap stage to the per-tenant carving: up to
    ``tenants`` disjoint (range, Mesh) slots in priority order (slot 0 =
    largest chunk = highest-priority tenant); ``bg`` is always slot 0.
    ``stage_fg_range`` gives the foreground's *actual* device window per
    stage — during a gap stage the fg occupies a strict prefix of
    ``fg_range``, and every bg range is disjoint from it.
    """

    fg_range: Tuple[int, int]
    fg_mesh: Mesh
    bg: Dict[int, Tuple[Tuple[int, int], Mesh]]
    stage_fg_range: Dict[int, Tuple[int, int]]
    bg_tenants: Dict[int, Tuple[Optional[Tuple[Tuple[int, int], Mesh]], ...]] = field(
        default_factory=dict
    )

    def bg_mesh(self, stage_index: int) -> Optional[Mesh]:
        hit = self.bg.get(stage_index)
        return hit[1] if hit else None

    def tenant_mesh(self, stage_index: int, slot: int) -> Optional[Mesh]:
        slots = self.bg_tenants.get(stage_index, ())
        if slot >= len(slots) or slots[slot] is None:
            return None
        return slots[slot][1]


def split_mesh_for_plan(plan, *, devices: Optional[Sequence] = None,
                        fg_model: int = 1, bg_model: int = 1,
                        tenants: int = 1,
                        tenant_quanta: Optional[Sequence[int]] = None,
                        ) -> PlanSubmeshes:
    """Carve the device set into the plan's fg submesh + per-gap bg submeshes.

    For each ``GapWindow`` the free set is ``plan.free_device_ranges(stage)``
    — the gap's idle devices minus any ``BranchPlacement`` ranges hosting
    parallel block branches *during that stage* — packed into up to
    ``tenants`` disjoint ``bg_model``-aligned chunks (``pack_ranges``,
    largest chunk first for the highest-priority tenant).  Raises when the
    process has fewer devices than the plan assumes.

    ``tenant_quanta`` switches to the slot-aware per-tenant mode: slot *i*'s
    chunk is aligned to (and its submesh model width is) ``tenant_quanta[i]``
    instead of the global ``bg_model``; a slot whose quantum no chunk can
    satisfy gets ``None`` in ``bg_tenants`` (the tenant is dropped from that
    gap — admission control / the starvation rotation decide what to do).
    """
    devs = list(devices) if devices is not None else jax.devices()
    if len(devs) < plan.num_gpus:
        raise ValueError(
            f"plan wants {plan.num_gpus} devices, process has {len(devs)}"
        )
    stages = plan.stages()
    fg_peak = max(s.gpus for s in stages)
    if fg_peak % fg_model:
        fg_model = 1
    fg_mesh = submesh_from_range(0, fg_peak, model=fg_model, devices=devs)
    bg: Dict[int, Tuple[Tuple[int, int], Mesh]] = {}
    bg_tenants: Dict[int, Tuple[Optional[Tuple[Tuple[int, int], Mesh]], ...]] = {}
    stage_fg: Dict[int, Tuple[int, int]] = {
        i: (0, s.gpus) for i, s in enumerate(stages)
    }
    quanta = (normalize_quanta(tenant_quanta, tenants)
              if tenant_quanta is not None else None)
    for gap in plan.gaps():
        free = plan.free_device_ranges(gap.stage_index)
        chunks = pack_ranges(free, tenants,
                             quantum=quanta if quanta is not None else bg_model)
        if not chunks or all(c is None for c in chunks):
            continue
        slots = tuple(
            None if c is None else (
                c, submesh_from_range(
                    c[0], c[1],
                    model=quanta[slot] if quanta is not None else bg_model,
                    devices=devs,
                )
            )
            for slot, c in enumerate(chunks)
        )
        bg_tenants[gap.stage_index] = slots
        bg[gap.stage_index] = next(s for s in slots if s is not None)
    return PlanSubmeshes(fg_range=(0, fg_peak), fg_mesh=fg_mesh, bg=bg,
                         stage_fg_range=stage_fg, bg_tenants=bg_tenants)


# ---------------------------------------------------------------------------
# Serving submeshes (prefill/decode disaggregation — ISSUE 9)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ServingSubmeshes:
    """Disjoint prefill/decode submeshes for a disaggregated serving engine.

    Built by carving a ``serving_plan`` with ``split_mesh_for_plan``:
    prefill is the plan's foreground stage on [0, n_prefill), decode is the
    gap's largest bg chunk.  The ranges are positional device indices, so
    ``disjoint`` is checkable without touching the device objects.
    """

    prefill_range: Tuple[int, int]
    prefill_mesh: Mesh
    decode_range: Tuple[int, int]
    decode_mesh: Mesh

    def disjoint(self) -> bool:
        (ps, pe), (ds, de) = self.prefill_range, self.decode_range
        return pe <= ds or de <= ps

    def device_sets_disjoint(self) -> bool:
        """The ground-truth check: no physical device in both meshes."""
        p = {d.id for d in self.prefill_mesh.devices.flat}
        q = {d.id for d in self.decode_mesh.devices.flat}
        return not (p & q)


def split_mesh_for_serving(n_prefill: int, *,
                           devices: Optional[Sequence] = None,
                           prefill_model: int = 1,
                           decode_model: int = 1) -> ServingSubmeshes:
    """Carve the device set into disjoint prefill + decode submeshes.

    Reuses the ``split_mesh_for_plan`` carving over a ``serving_plan`` —
    prefill as the foreground stage, decode as its burst gap — so the
    positional-disjointness invariant of ``submesh_from_range`` carries
    over: the two submeshes can never share a device.
    """
    devs = list(devices) if devices is not None else jax.devices()
    plan = serving_plan(len(devs), n_prefill)
    split = split_mesh_for_plan(plan, devices=devs, fg_model=prefill_model,
                                bg_model=decode_model)
    hit = split.bg.get(0)
    if hit is None:
        raise ValueError(
            f"no decode carving: {len(devs) - n_prefill} free devices can't "
            f"fit a decode submesh with model={decode_model}"
        )
    (ds, de), dmesh = hit
    return ServingSubmeshes(
        prefill_range=split.fg_range, prefill_mesh=split.fg_mesh,
        decode_range=(ds, de), decode_mesh=dmesh,
    )
