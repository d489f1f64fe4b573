"""The benchmark's data layer: ``BENCHMARK.json`` and the files it names.

A cell (an entry of ``workloads``) names a configuration and a traffic mix.
Each lives in a file of its own, found by name:

- ``configs/<config>.json``: the model as it is run (published widths, the
  keys cut from the source, the sizes assumed, the training recipe, and
  ``"reference"``, the name of its reference);
- ``references/<reference>.py``: the model's plain float32 reference, its
  parameter layout, the law of its seeded weights and the FLOPs its
  training step requires (``REFERENCE_FUNCTIONS``; ``chipbench.reference``
  trains it);
- ``traffic/<traffic>.json``: what one run feeds the program (sequence,
  batch, mesh, token skew, background tenants, warm-up and schedule steps);
- ``workloads/<cell>.json``: the limits of the comparison that decides
  ``correct`` in that cell;
- ``metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import functools
import importlib.util
import json
import re
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parents[1]  # benchmarks/chip
ROOT = HERE.parents[1]  # the checkout
NAME_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT_RE = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
REFERENCES = HERE / "references"
# what a reference module gives: the program's parameter layout, the law of
# each seeded weight by its leaf path, the plain loss (``prec="fp8"`` the
# control), the FLOPs a training step requires
REFERENCE_FUNCTIONS = ("param_shapes", "init", "loss", "train_flops_per_token")


def _load(path: Path) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return _load(ROOT / "BENCHMARK.json")


@dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: Dict[str, Any]  # configs/<config>.json
    traffic: Dict[str, Any]  # traffic/<traffic>.json
    limits: Dict[str, float]  # workloads/<cell>.json "limits"
    end_to_end: List[Dict[str, Any]]  # the cell's end-to-end metrics
    per_layer: List[Dict[str, Any]]  # the cell's per-layer metrics


def _reports(metric: Dict[str, Any], cell: str, e2e_names: List[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in e2e_names


def cell(name: str, bench: Optional[Dict[str, Any]] = None) -> Cell:
    bench = bench or benchmark()
    [w] = [w for w in bench["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    [c] = [c for c in bench["configs"] if c["name"] == w["config"]]
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = [m["name"] for m in e2e]
    per = [m for m in bench["per_layer"] if _reports(m, name, names)]
    config = _load(ROOT / c["file"])
    reference(config)  # refuses a configuration without a usable reference
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=config,
        traffic=_load(HERE / "traffic" / f"{w['traffic']}.json"),
        limits=_load(HERE / "workloads" / f"{name}.json")["limits"],
        end_to_end=e2e,
        per_layer=per,
    )


def reader_path(metric: str) -> Path:
    return HERE / "metrics" / f"{metric}.py"


@functools.lru_cache(maxsize=None)
def load_module(path: Path) -> ModuleType:
    """The Python file ``path`` as a module, loaded once per process."""
    name = "chipbench_" + re.sub(r"\W", "_", f"{path.parent.name}_{path.stem}")
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def reference(config: Dict[str, Any]) -> ModuleType:
    """The reference module that the configuration ``config`` names.
    There is no default: a configuration without ``"reference"``, or one
    that names no module under ``REFERENCES`` giving each of
    ``REFERENCE_FUNCTIONS``, is refused."""
    name = config.get("reference")
    if not isinstance(name, str) or not NAME_RE.match(name):
        raise ValueError(f"the configuration names no reference "
                         f"(\"reference\": {name!r}); each names its module "
                         f"under {REFERENCES}")
    path = REFERENCES / f"{name}.py"
    if not path.is_file():
        raise ValueError(f"no reference {name!r}: {path} does not exist")
    mod = load_module(path)
    missing = [f for f in REFERENCE_FUNCTIONS
               if not callable(getattr(mod, f, None))]
    if missing:
        raise ValueError(f"reference {name!r} lacks {missing}")
    return mod
