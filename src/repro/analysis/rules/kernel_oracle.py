"""kernel-oracle-pairing: every exported Pallas kernel has a contract.

The kernels package's correctness story (DESIGN-by-oracle, PR 3/4) is:
each Pallas kernel is validated against a pure-jnp reference in
``kernels/ref.py`` — sweeping shapes/dtypes in interpret mode on CPU and
compiled on TPU.  A kernel without a registered oracle, or without an
interpret-mode test, is unverifiable on this container and ships on
trust.  This rule closes the loop statically:

* an *exported kernel* is a public module-level function in a
  ``kernels/`` module (other than ``ref.py``) that invokes
  ``pl.pallas_call`` directly, or publicly wraps one that does;
* every exported kernel must be a key of the ``ORACLES`` table in the
  sibling ``kernels/ref.py`` (falling back to a ``<kernel>_ref``
  function there);
* when the scanned file set includes test files (``test_*.py``), every
  exported kernel must be referenced by name in at least one test file
  that exercises interpret mode (``interpret=True``) — so CLI runs over
  ``src/`` alone still check pairing, and the CI run over
  ``src/ tests/`` checks coverage too.
"""
from __future__ import annotations

import ast
import os
from typing import Iterator

from repro.analysis import astutil
from repro.analysis.engine import Finding, SourceFile

RULE_ID = "kernel-oracle-pairing"

NON_KERNEL_FILES = {"ref.py", "__init__.py"}


def _is_kernels_module(src: SourceFile) -> bool:
    parts = src.relpath.split("/")
    return "kernels" in parts[:-1] and \
        parts[-1] not in NON_KERNEL_FILES


def _kernels_dir(src: SourceFile) -> str:
    dirs = src.relpath.split("/")[:-1]
    idx = len(dirs) - 1 - dirs[::-1].index("kernels")
    return "/".join(dirs[:idx + 1])


def _exported_kernels(src: SourceFile) -> list[tuple[str, int]]:
    """Public functions that (transitively, one hop, same module) call
    ``pl.pallas_call``."""
    direct: set[str] = set()
    fns = [fn for fn in src.tree.body
           if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))]
    for fn in fns:
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = astutil.call_name(node)
                if callee and \
                        astutil.last_segment(callee) == "pallas_call":
                    direct.add(fn.name)
                    break
    exported: dict[str, int] = {}
    for fn in fns:
        if fn.name.startswith("_"):
            continue
        if fn.name in direct:
            exported[fn.name] = fn.lineno
            continue
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                callee = astutil.call_name(node)
                if callee and astutil.last_segment(callee) in direct:
                    exported[fn.name] = fn.lineno
                    break
    return sorted(exported.items())


def _oracle_names(ref_src: SourceFile) -> set[str]:
    """Keys of the ORACLES table plus ``<name>_ref`` function stems."""
    names: set[str] = set()
    for node in ref_src.tree.body:
        if isinstance(node, ast.Assign) and len(node.targets) == 1 and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "ORACLES" and \
                isinstance(node.value, ast.Dict):
            for k in node.value.keys:
                if isinstance(k, ast.Constant) and isinstance(k.value,
                                                              str):
                    names.add(k.value)
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name.endswith("_ref"):
            names.add(node.name[:-len("_ref")])
    return names


def _test_interpret_refs(files: list[SourceFile]) -> tuple[bool,
                                                           set[str]]:
    """(any test files present, kernel names referenced in a test file
    that uses interpret=True)."""
    any_tests = False
    referenced: set[str] = set()
    for src in files:
        if not os.path.basename(src.relpath).startswith("test_"):
            continue
        any_tests = True
        uses_interpret = any(
            kw.arg == "interpret" and
            isinstance(kw.value, ast.Constant) and kw.value.value is True
            for node in ast.walk(src.tree)
            if isinstance(node, ast.Call) for kw in node.keywords)
        if not uses_interpret:
            continue
        for node in ast.walk(src.tree):
            if isinstance(node, ast.Name):
                referenced.add(node.id)
            elif isinstance(node, ast.Attribute):
                referenced.add(node.attr)
    return any_tests, referenced


def check_project(files: list[SourceFile]) -> Iterator[Finding]:
    kernel_mods = [s for s in files if _is_kernels_module(s)]
    if not kernel_mods:
        return
    refs_by_dir = {_kernels_dir(s): s for s in files
                   if s.relpath.endswith("/ref.py")
                   and "kernels" in s.relpath.split("/")}
    any_tests, tested = _test_interpret_refs(files)
    for src in kernel_mods:
        kernels = _exported_kernels(src)
        if not kernels:
            continue
        ref_src = refs_by_dir.get(_kernels_dir(src))
        oracles = _oracle_names(ref_src) if ref_src is not None else set()
        for name, line in kernels:
            if ref_src is None:
                yield Finding(
                    file=src.relpath, line=line, rule=RULE_ID,
                    severity="error",
                    message=(f"kernel `{name}` has no sibling "
                             f"kernels/ref.py — every Pallas kernel "
                             f"needs a pure-jnp oracle"))
            elif name not in oracles:
                yield Finding(
                    file=src.relpath, line=line, rule=RULE_ID,
                    severity="error",
                    message=(f"kernel `{name}` is not registered in "
                             f"kernels/ref.py (add an ORACLES entry or "
                             f"a `{name}_ref` oracle)"))
            if any_tests and name not in tested:
                yield Finding(
                    file=src.relpath, line=line, rule=RULE_ID,
                    severity="error",
                    message=(f"kernel `{name}` is never referenced by an "
                             f"interpret-mode test (interpret=True) in "
                             f"the scanned test files"))
