"""Repo AST lint (Shardlint layer 2) — ``python -m repro.analysis.lint``.

Encodes the repo's hard-won sharding rules as checkable code. Pure
stdlib (``ast``) on purpose: CI's lint job runs it before any jax wheel
is installed, and it must stay runnable on a bare interpreter.

=====  ====================================================================
rule   what it forbids (and the incident behind it)
=====  ====================================================================
SL001  importing ``jax.experimental.shard_map`` anywhere (no allowlist)
       — the experimental module is the pre-0.5 API; every region uses
       ``jax.shard_map`` with ``axis_names``/``check_vma``
SL002  ``ragged_dot`` outside the documented allowlist
       (``kernels/ref.py``) — XLA's SPMD partitioner rewrites its
       group_sizes operand incorrectly on ep/tp meshes (PR 6)
SL003  ``jax.device_get`` / ``np.asarray`` inside traced step-building
       modules (train/ models/ optim/ parallel/ core/) — a host sync
       baked into the step serializes every iteration
SL004  any occurrence of the retired ``KERNEL_CONFIG`` / ``ATTN_IMPL``
       aliases — plan-scoped ``KernelPlan`` replaced the process-global
       knobs, and the PR 4 compatibility shims are now deleted; reads,
       writes, and imports alike are tombstoned (no allowlist)
=====  ====================================================================

Allowlists are path *suffixes* (posix-normalized), so the lint works on
absolute or relative invocations. A synthetic file outside the repo gets
no allowlist match — which is exactly what the CI self-test relies on.
"""
from __future__ import annotations

import argparse
import ast
import sys
from pathlib import Path
from typing import Iterator, List, Tuple

# rule -> path suffixes where the construct is the documented owner
ALLOWLIST = {
    "SL001": (),
    "SL002": ("src/repro/kernels/ref.py",),
    "SL003": (),
    # SL004 has no owners left: the PR 4 aliases are deleted, the symbols
    # are tombstones — any mention (read, write, or import) is a violation
    "SL004": (),
}

# SL003 applies only inside modules whose code ends up in the traced step
TRACED_MODULE_DIRS = ("src/repro/train/", "src/repro/models/",
                      "src/repro/optim/", "src/repro/parallel/",
                      "src/repro/core/")

_DEPRECATED_ALIASES = ("KERNEL_CONFIG", "ATTN_IMPL")

Violation = Tuple[str, str, int, str]     # (rule, path, lineno, message)


def _dotted(node) -> str:
    """'jax.experimental.shard_map' for an Attribute/Name chain ('' when
    the chain bottoms out in something dynamic)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return ""


def _allowed(rule: str, posix_path: str, extra=()) -> bool:
    return any(posix_path.endswith(sfx)
               for sfx in tuple(ALLOWLIST.get(rule, ())) + tuple(extra))


def _np_aliases(tree: ast.AST) -> set:
    """Module-level names bound to the numpy module ('np', 'numpy')."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name == "numpy":
                    names.add(a.asname or "numpy")
    return names


def lint_source(source: str, path: str, *,
                traced_dirs=TRACED_MODULE_DIRS,
                allow_extra=()) -> List[Violation]:
    """Lint one file's source. ``path`` is used for allowlist matching and
    reporting only. ``traced_dirs`` scopes SL003 (tests override it to
    force a synthetic file into 'traced' territory)."""
    posix = Path(path).as_posix()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as e:
        return [("SL000", path, e.lineno or 0, f"syntax error: {e.msg}")]

    out: List[Violation] = []
    is_traced = any(d in posix for d in traced_dirs)
    np_names = _np_aliases(tree)

    def emit(rule, node, msg):
        if not _allowed(rule, posix, allow_extra):
            out.append((rule, path, getattr(node, "lineno", 0), msg))

    for node in ast.walk(tree):
        # SL001 — raw shard_map imports
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.name.startswith("jax.experimental.shard_map"):
                    emit("SL001", node,
                         f"import {a.name}: use jax.shard_map")
        elif isinstance(node, ast.ImportFrom):
            mod = node.module or ""
            if mod.startswith("jax.experimental.shard_map"):
                emit("SL001", node,
                     f"from {mod} import ...: use jax.shard_map")
            elif mod == "jax.experimental" and any(
                    a.name == "shard_map" for a in node.names):
                emit("SL001", node,
                     "from jax.experimental import shard_map: use "
                     "jax.shard_map")

        dotted = _dotted(node) if isinstance(node, ast.Attribute) else ""

        # SL001 — attribute use without import (jax.experimental.shard_map.x)
        if dotted.startswith("jax.experimental.shard_map"):
            emit("SL001", node,
                 f"{dotted}: use jax.shard_map")

        # SL002 — ragged_dot outside the allowlist
        if isinstance(node, ast.Attribute) and node.attr == "ragged_dot":
            emit("SL002", node,
                 f"{dotted or 'ragged_dot'}: GSPMD corrupts ragged_dot's "
                 f"group_sizes on ep/tp meshes — use kernels.ops.gmm or "
                 f"extend the SL002 allowlist with a justification")

        # SL003 — host transfers in traced step-building modules
        if is_traced and isinstance(node, ast.Attribute):
            if dotted == "jax.device_get":
                emit("SL003", node,
                     "jax.device_get inside a traced step-building "
                     "module: host sync per step")
            elif node.attr == "asarray" and dotted and \
                    dotted.split(".")[0] in np_names:
                emit("SL003", node,
                     f"{dotted}: numpy materialization inside a traced "
                     f"step-building module (use jnp.asarray)")

        # SL004 — ANY occurrence of the retired module-global kernel knobs:
        # bare names, attribute access (ops.KERNEL_CONFIG), and imports.
        # The aliases are deleted; a surviving mention is dead code that
        # would NameError (or worse, resurrect the global) at runtime.
        if isinstance(node, ast.Name) and node.id in _DEPRECATED_ALIASES:
            emit("SL004", node,
                 f"{node.id} is retired: scope kernel knobs with "
                 f"KernelPlan / use_kernel_plan instead")
        elif isinstance(node, ast.Attribute) and \
                node.attr in _DEPRECATED_ALIASES:
            emit("SL004", node,
                 f"{_dotted(node) or node.attr} is retired: scope kernel "
                 f"knobs with KernelPlan / use_kernel_plan instead")
        elif isinstance(node, ast.ImportFrom) and any(
                a.name in _DEPRECATED_ALIASES for a in node.names):
            emit("SL004", node,
                 "importing a retired alias (KERNEL_CONFIG/ATTN_IMPL): "
                 "scope kernel knobs with KernelPlan / use_kernel_plan")
    return out


def iter_py_files(paths) -> Iterator[Path]:
    for p in paths:
        p = Path(p)
        if p.is_dir():
            for f in sorted(p.rglob("*.py")):
                if "__pycache__" not in f.parts:
                    yield f
        elif p.suffix == ".py":
            yield p


def lint_paths(paths, *, traced_dirs=TRACED_MODULE_DIRS,
               allow_extra=()) -> List[Violation]:
    out: List[Violation] = []
    for f in iter_py_files(paths):
        try:
            src = f.read_text()
        except (OSError, UnicodeDecodeError) as e:
            out.append(("SL000", str(f), 0, f"unreadable: {e}"))
            continue
        out.extend(lint_source(src, str(f), traced_dirs=traced_dirs,
                               allow_extra=allow_extra))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.lint",
        description="Shardlint AST rules SL001-SL004 (stdlib-only)")
    ap.add_argument("paths", nargs="*", default=None,
                    help="files/dirs to lint (default: src tests "
                         "benchmarks under the cwd)")
    ap.add_argument("--traced", action="append", default=None,
                    help="extra path fragment treated as a traced "
                         "step-building module for SL003 (tests use this "
                         "on synthetic files)")
    ap.add_argument("--allow", action="append", default=None,
                    help="extra allowlisted path suffix (all rules)")
    args = ap.parse_args(argv)

    paths = args.paths or [p for p in ("src", "tests", "benchmarks")
                           if Path(p).is_dir()]
    if not paths:
        print("shardlint: no paths to lint", file=sys.stderr)
        return 2
    traced = TRACED_MODULE_DIRS + tuple(args.traced or ())
    vs = lint_paths(paths, traced_dirs=traced,
                    allow_extra=tuple(args.allow or ()))
    for rule, path, lineno, msg in vs:
        print(f"{path}:{lineno}: {rule} {msg}")
    n = sum(1 for v in vs)
    files = sum(1 for _ in iter_py_files(paths))
    if n:
        print(f"shardlint: {n} violation(s) in {files} file(s)",
              file=sys.stderr)
        return 1
    print(f"shardlint: {files} file(s) clean")
    return 0


if __name__ == "__main__":
    sys.exit(main())
