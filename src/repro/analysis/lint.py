"""AST linter for the JAX hazards this repo has actually shipped.

Five rules, each a bug class from a past PR:

- ``JH001`` jit-in-hot-path: ``jax.jit(...)`` immediately invoked, built
  inside a loop, or built inside a per-step/per-request function body
  without being cached on an attribute/subscript — the PR 9 prefill
  retracing bug (every ``generate()`` call recompiled the prefill).
  Factories (``make_*``/``build_*``/``jit_*``) and cached-assignment
  idioms (``self._fn = jax.jit(...)``, ``cache[k] = jax.jit(...)``,
  ``return jax.jit(...)``) are exempt.
- ``JH002`` wall-clock-in-virtual-clock-module: ``time.time``/
  ``time.sleep`` in modules that run on a virtual clock (``sim/``,
  ``serve/scheduler.py``) — a single wall-clock read desynchronizes a
  deterministic replay.  ``time.perf_counter`` is allowed: the serving
  scheduler *measures* op durations to advance its virtual clock.
- ``JH003`` assert-on-traced: Python ``assert`` over ``jnp``/``jax``
  expressions — under ``jit`` the test is a tracer, so the assert either
  fails at trace time or silently passes on the abstract value.
- ``JH004`` pspec-unknown-axis: string axis names in
  ``PartitionSpec``/``P`` constructors outside the declared mesh-axis
  vocabulary {pod, data, model} — a typo'd axis silently replicates.
- ``JH005`` make-mesh-axis-types: ``jax.make_mesh(...)`` without
  ``axis_types=`` — since JAX 0.9 its axes default to ``Explicit``, and
  every ``with_sharding_constraint`` in the models then raises ("can only
  refer to Auto axes").

Intentional sites live in the committed allowlist
(``lint_allowlist.txt`` next to this module): one line per site,
``RULE  path-suffix  qualname  # justification``.  Run as::

    python -m repro.analysis.lint src/

Exit status 1 when any unallowlisted finding remains.
"""
from __future__ import annotations

import argparse
import ast
import sys
from dataclasses import dataclass
from pathlib import Path
from typing import Iterable, List, Optional, Sequence, Tuple

MESH_AXIS_VOCAB = frozenset({"pod", "data", "model"})

# modules that must never read the wall clock (deterministic replay)
VIRTUAL_CLOCK_PARTS = ("sim",)
VIRTUAL_CLOCK_FILES = ("serve/scheduler.py",)

# function-name markers for per-step/per-request hot paths
HOT_MARKERS = ("step", "generate", "admit", "pump", "decode", "prefill",
               "serve", "handle_", "retire", "tick")
# factory prefixes: functions that exist to build a jitted callable once
FACTORY_PREFIXES = ("make_", "build_", "_make_", "_build_", "jit_")


@dataclass(frozen=True)
class LintFinding:
    path: str        # posix path as given on the command line
    line: int
    col: int
    rule: str
    message: str
    qualname: str    # innermost enclosing function ('' at module level)

    def __str__(self) -> str:
        return (f"{self.path}:{self.line}:{self.col}: {self.rule} "
                f"{self.message}")


# -- allowlist --------------------------------------------------------------


@dataclass(frozen=True)
class AllowEntry:
    rule: str
    path_suffix: str
    qualname: str
    justification: str


def load_allowlist(path: Optional[Path] = None) -> List[AllowEntry]:
    if path is None:
        path = Path(__file__).with_name("lint_allowlist.txt")
    if not path.exists():
        return []
    out = []
    for raw in path.read_text().splitlines():
        line, _, comment = raw.partition("#")
        fields = line.split()
        if not fields:
            continue
        if len(fields) != 3:
            raise ValueError(
                f"{path}: malformed allowlist line {raw!r} "
                f"(want: RULE path-suffix qualname  # justification)")
        out.append(AllowEntry(fields[0], fields[1], fields[2],
                              comment.strip()))
    return out


def _allowed(f: LintFinding, allow: Sequence[AllowEntry]) -> bool:
    p = Path(f.path).as_posix()
    return any(
        a.rule == f.rule and p.endswith(a.path_suffix)
        and a.qualname == f.qualname
        for a in allow
    )


# -- AST helpers ------------------------------------------------------------


def _attach_parents(tree: ast.AST) -> None:
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child._lint_parent = node  # type: ignore[attr-defined]


def _parents(node: ast.AST) -> Iterable[ast.AST]:
    cur = getattr(node, "_lint_parent", None)
    while cur is not None:
        yield cur
        cur = getattr(cur, "_lint_parent", None)


def _enclosing_funcs(node: ast.AST) -> List[ast.AST]:
    return [p for p in _parents(node)
            if isinstance(p, (ast.FunctionDef, ast.AsyncFunctionDef))]


def _qualname(node: ast.AST) -> str:
    names = [f.name for f in _enclosing_funcs(node)]
    for p in _parents(node):
        if isinstance(p, ast.ClassDef):
            names.append(p.name)
            break
    return ".".join(reversed(names))


def _is_jax_jit(call: ast.Call) -> bool:
    f = call.func
    return (isinstance(f, ast.Attribute) and f.attr == "jit"
            and isinstance(f.value, ast.Name) and f.value.id == "jax")


def _string_leaves(node: ast.AST) -> Iterable[Tuple[ast.AST, str]]:
    for n in ast.walk(node):
        if isinstance(n, ast.Constant) and isinstance(n.value, str):
            yield n, n.value


# -- rules ------------------------------------------------------------------


def _check_jit(tree: ast.AST, path: str) -> List[LintFinding]:
    out = []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and _is_jax_jit(node)):
            continue
        parent = getattr(node, "_lint_parent", None)
        qn = _qualname(node)

        # jax.jit(f)(x): compiled object thrown away after one call
        if isinstance(parent, ast.Call) and parent.func is node:
            out.append(LintFinding(
                path, node.lineno, node.col_offset, "JH001",
                "jax.jit(...) immediately invoked — the compiled callable "
                "is discarded and every call retraces", qn))
            continue

        # cached-assignment idioms are safe anywhere
        if isinstance(parent, ast.Return):
            continue
        if isinstance(parent, ast.Assign) and any(
                isinstance(t, (ast.Attribute, ast.Subscript))
                for t in parent.targets):
            continue

        funcs = _enclosing_funcs(node)
        in_loop = any(
            isinstance(p, (ast.For, ast.While, ast.AsyncFor))
            for p in _parents(node)
        )
        innermost = funcs[0].name if funcs else ""
        is_factory = innermost.startswith(FACTORY_PREFIXES)
        is_hot = (not is_factory and any(
            m in innermost.lower() for m in HOT_MARKERS))
        if in_loop:
            out.append(LintFinding(
                path, node.lineno, node.col_offset, "JH001",
                "jax.jit(...) inside a loop without an attribute/subscript "
                "cache — recompiles every iteration", qn))
        elif is_hot:
            out.append(LintFinding(
                path, node.lineno, node.col_offset, "JH001",
                f"jax.jit(...) in per-step/per-request function "
                f"{innermost!r} without an attribute/subscript cache — "
                f"retraces on every call", qn))
    return out


def _is_virtual_clock_module(path: str) -> bool:
    p = Path(path).as_posix()
    if any(p.endswith(f) for f in VIRTUAL_CLOCK_FILES):
        return True
    return any(part in VIRTUAL_CLOCK_PARTS for part in Path(p).parts)


def _check_wallclock(tree: ast.AST, path: str) -> List[LintFinding]:
    if not _is_virtual_clock_module(path):
        return []
    out = []
    for node in ast.walk(tree):
        if (isinstance(node, ast.Attribute)
                and node.attr in ("time", "sleep")
                and isinstance(node.value, ast.Name)
                and node.value.id == "time"):
            out.append(LintFinding(
                path, node.lineno, node.col_offset, "JH002",
                f"time.{node.attr} in a virtual-clock module — wall-clock "
                f"reads desynchronize deterministic replay "
                f"(time.perf_counter for measured durations is fine)",
                _qualname(node)))
        if isinstance(node, ast.ImportFrom) and node.module == "time":
            bad = [a.name for a in node.names
                   if a.name in ("time", "sleep")]
            if bad:
                out.append(LintFinding(
                    path, node.lineno, node.col_offset, "JH002",
                    f"from time import {', '.join(bad)} in a virtual-clock "
                    f"module", _qualname(node)))
    return out


def _check_traced_assert(tree: ast.AST, path: str) -> List[LintFinding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Assert):
            continue
        for n in ast.walk(node.test):
            if isinstance(n, ast.Name) and n.id in ("jnp", "jax"):
                out.append(LintFinding(
                    path, node.lineno, node.col_offset, "JH003",
                    "assert over a jax/jnp expression — under jit the test "
                    "is a tracer; use checkify or a host callback",
                    _qualname(node)))
                break
    return out


def _check_pspec_axes(tree: ast.AST, path: str) -> List[LintFinding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        name = (f.id if isinstance(f, ast.Name)
                else f.attr if isinstance(f, ast.Attribute) else None)
        if name not in ("P", "PartitionSpec"):
            continue
        for leaf, s in _string_leaves(
                ast.Tuple(elts=list(node.args), ctx=ast.Load())):
            if s not in MESH_AXIS_VOCAB:
                out.append(LintFinding(
                    path, leaf.lineno, leaf.col_offset, "JH004",
                    f"pspec axis {s!r} outside the mesh-axis vocabulary "
                    f"{sorted(MESH_AXIS_VOCAB)} — an unknown axis silently "
                    f"replicates", _qualname(node)))
    return out


def _check_make_mesh(tree: ast.AST, path: str) -> List[LintFinding]:
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        if not (isinstance(f, ast.Attribute) and f.attr == "make_mesh"
                and isinstance(f.value, ast.Name) and f.value.id == "jax"):
            continue
        if any(k.arg == "axis_types" for k in node.keywords):
            continue
        out.append(LintFinding(
            path, node.lineno, node.col_offset, "JH005",
            "jax.make_mesh(...) without axis_types= — its axes default to "
            "Explicit, which with_sharding_constraint rejects; pass "
            "(AxisType.Auto,) * ndim", _qualname(node)))
    return out


RULES = (_check_jit, _check_wallclock, _check_traced_assert,
         _check_pspec_axes, _check_make_mesh)


# -- driver -----------------------------------------------------------------


def lint_file(path: Path, display: Optional[str] = None) -> List[LintFinding]:
    src = path.read_text()
    try:
        tree = ast.parse(src, filename=str(path))
    except SyntaxError as e:
        return [LintFinding(display or str(path), e.lineno or 0, 0,
                            "JH000", f"syntax error: {e.msg}", "")]
    _attach_parents(tree)
    out: List[LintFinding] = []
    for rule in RULES:
        out.extend(rule(tree, display or str(path)))
    return out


def lint_paths(paths: Sequence[str],
               allowlist: Optional[Sequence[AllowEntry]] = None,
               ) -> Tuple[List[LintFinding], List[LintFinding]]:
    """Lint files/trees; returns (findings, suppressed-by-allowlist)."""
    allow = load_allowlist() if allowlist is None else list(allowlist)
    files: List[Path] = []
    for p in paths:
        pp = Path(p)
        files.extend(sorted(pp.rglob("*.py")) if pp.is_dir() else [pp])
    findings: List[LintFinding] = []
    suppressed: List[LintFinding] = []
    for f in files:
        for hit in lint_file(f, display=f.as_posix()):
            (suppressed if _allowed(hit, allow) else findings).append(hit)
    return findings, suppressed


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="JAX-hazard linter (jit retracing, wall-clock in "
                    "virtual-clock modules, traced asserts, unknown pspec "
                    "axes, meshes without Auto axis types)")
    ap.add_argument("paths", nargs="+", help="files or directories to lint")
    ap.add_argument("--allowlist", type=Path, default=None,
                    help="override the committed allowlist file")
    args = ap.parse_args(argv)
    allow = (load_allowlist(args.allowlist) if args.allowlist
             else load_allowlist())
    findings, suppressed = lint_paths(args.paths, allowlist=allow)
    for f in findings:
        print(f)
    if suppressed:
        print(f"({len(suppressed)} allowlisted finding(s) suppressed)",
              file=sys.stderr)
    if findings:
        print(f"{len(findings)} finding(s)", file=sys.stderr)
        return 1
    print(f"lint clean ({len(suppressed)} allowlisted)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
