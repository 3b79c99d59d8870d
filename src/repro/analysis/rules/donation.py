"""use-after-donate: a donated buffer is consumed by the call.

The streaming-AIO hot path updates its O(N) accumulators in place:
``jax.jit(..., donate_argnums=...)`` wrappers (``topology/edge.py``'s
``_absorb_jnp`` / ``_merge_jnp``), the Pallas ``aio_absorb`` /
``aio_merge`` kernels, and the shared ``absorb_trees`` /
``merge_trees`` update rules all consume the accumulator operands they
are given.  Reading such a buffer again before rebinding it raises a
deleted-array error at runtime — but only on backends where donation is
honored, which is exactly how the bug class escapes CPU CI.  This rule
finds the read statically.

Tracking is path-based within one function scope: after a donating call,
the dotted paths passed in donated positions (``num``, ``self.part.num``,
...) are *consumed*; any later read of the same path (or a deeper
attribute/subscript of it) before the path — or a prefix of it — is
rebound, is a finding.  Loop bodies are analyzed twice so an accumulator
consumed in iteration *t* and re-passed un-rebound in iteration *t+1*
is caught; branches are merged conservatively (consumed in either arm
=> consumed after the ``if``).

Donating callables are discovered three ways:

* a built-in table of this repo's known donating entry points,
* ``@functools.partial(jax.jit, donate_argnums=...)`` decorators in the
  scanned file,
* ``name = jax.jit(f, donate_argnums=...)`` bindings in the scanned file.
"""
from __future__ import annotations

import ast
from typing import Iterator

from repro.analysis import astutil
from repro.analysis.engine import Finding, SourceFile

RULE_ID = "use-after-donate"

#: callee last-segment -> ((positional argnum, consumed-path suffix), ...)
#: Suffixes let an object-valued argument consume only its donated
#: buffers: ``partial_merge(a, b)`` spends ``a.num``/``a.den`` but
#: ``a.count`` stays readable.
KNOWN_DONATING: dict[str, tuple[tuple[int, str], ...]] = {
    "aio_absorb": ((0, ""), (1, "")),
    "aio_merge": ((0, ""), (1, "")),
    "absorb_trees": ((0, ""), (1, "")),
    "merge_trees": ((0, ""), (1, "")),
    "partial_absorb": ((0, ".num"), (0, ".den")),
    "partial_merge": ((0, ".num"), (0, ".den")),
}


def _file_donating_map(tree: ast.AST) -> dict[str, tuple[tuple[int, str],
                                                         ...]]:
    table = dict(KNOWN_DONATING)
    for fn in astutil.functions(tree):
        nums = astutil.donated_argnums(fn)
        if nums:
            table[fn.name] = tuple((n, "") for n in nums)
    for name, nums in astutil.jit_assignment_donations(tree).items():
        table[name] = tuple((n, "") for n in nums)
    return table


def _exits(body: list) -> bool:
    """Control cannot fall off the end of this statement list."""
    return bool(body) and isinstance(
        body[-1], (ast.Return, ast.Raise, ast.Continue, ast.Break))


class _Flow:
    """Linear consumed-path propagation over one function body."""

    def __init__(self, table):
        self.table = table
        self.hits: set[tuple[int, str, str, int]] = set()

    # -- expression side -------------------------------------------------

    def _maximal_reads(self, expr: ast.AST) -> Iterator[tuple[int, str]]:
        parents = astutil.build_parents(expr)
        for node in ast.walk(expr):
            if not isinstance(node, (ast.Name, ast.Attribute)):
                continue
            if not isinstance(getattr(node, "ctx", None), ast.Load):
                continue
            parent = parents.get(node)
            if isinstance(parent, ast.Attribute) and parent.value is node:
                continue        # inner link of a longer chain
            p = astutil.dotted_path(node)
            if p is not None:
                yield node.lineno, p

    def check_reads(self, expr: ast.AST, env: dict) -> None:
        if expr is None:
            return
        for line, path in self._maximal_reads(expr):
            for consumed, (cline, callee) in env.items():
                if path == consumed or path.startswith(consumed + "."):
                    self.hits.add((line, path, callee, cline))

    def activate(self, stmt: ast.AST, env: dict) -> None:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            callee = astutil.call_name(node)
            if callee is None:
                continue
            spec = self.table.get(astutil.last_segment(callee))
            if spec is None:
                continue
            for argnum, suffix in spec:
                if argnum < len(node.args) and \
                        not isinstance(node.args[argnum], ast.Starred):
                    p = astutil.dotted_path(node.args[argnum])
                    if p is not None:
                        env[p + suffix] = (node.lineno,
                                           astutil.last_segment(callee))

    @staticmethod
    def clear(paths: Iterator[str], env: dict) -> None:
        for t in paths:
            for consumed in list(env):
                if consumed == t or consumed.startswith(t + "."):
                    del env[consumed]

    # -- statement side --------------------------------------------------

    def block(self, stmts, env: dict) -> dict:
        for stmt in stmts:
            env = self.stmt(stmt, env)
        return env

    def _loop(self, stmt, env: dict, *, header) -> dict:
        self.check_reads(header, env)
        self.activate(header, env)
        if isinstance(stmt, ast.For):
            self.clear(astutil.assigned_paths(stmt.target), env)
        # two passes: the second sees the consumed-set the first left
        # behind, catching reads that only happen across the back edge
        env1 = self.block(stmt.body, dict(env))
        merged = {**env, **env1}
        env2 = self.block(stmt.body, dict(merged))
        out = {**merged, **env2}
        return self.block(stmt.orelse, out)

    def stmt(self, stmt: ast.AST, env: dict) -> dict:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return env          # separate scope, analyzed on its own
        if isinstance(stmt, ast.If):
            self.check_reads(stmt.test, env)
            self.activate(stmt.test, env)
            env_a = self.block(stmt.body, dict(env))
            env_b = self.block(stmt.orelse, dict(env))
            # a branch that exits (return/raise/...) contributes nothing
            # to the fallthrough state
            if _exits(stmt.body):
                env_a = {}
            if stmt.orelse and _exits(stmt.orelse):
                env_b = {}
            return {**env_a, **env_b}
        if isinstance(stmt, ast.For):
            return self._loop(stmt, env, header=stmt.iter)
        if isinstance(stmt, ast.While):
            return self._loop(stmt, env, header=stmt.test)
        if isinstance(stmt, ast.With):
            for item in stmt.items:
                self.check_reads(item.context_expr, env)
                if item.optional_vars is not None:
                    self.clear(astutil.assigned_paths(item.optional_vars),
                               env)
            return self.block(stmt.body, env)
        if isinstance(stmt, ast.Try):
            env_b = self.block(stmt.body, dict(env))
            outs = [env_b]
            for handler in stmt.handlers:
                outs.append(self.block(handler.body, dict(env_b)))
            merged: dict = {}
            for o in outs:
                merged.update(o)
            merged = self.block(stmt.orelse, merged)
            return self.block(stmt.finalbody, merged)
        if isinstance(stmt, ast.Assign):
            self.check_reads(stmt.value, env)
            self.activate(stmt, env)
            for target in stmt.targets:
                self.clear(astutil.assigned_paths(target), env)
            return env
        if isinstance(stmt, ast.AnnAssign):
            self.check_reads(stmt.value, env)
            self.activate(stmt, env)
            if stmt.value is not None:
                self.clear(astutil.assigned_paths(stmt.target), env)
            return env
        if isinstance(stmt, ast.AugAssign):
            # x += e reads x, then rebinds it
            self.check_reads(stmt.value, env)
            p = astutil.dotted_path(stmt.target)
            if p is not None:
                for consumed, (cline, callee) in env.items():
                    if p == consumed or p.startswith(consumed + "."):
                        self.hits.add((stmt.lineno, p, callee, cline))
            self.activate(stmt, env)
            self.clear(astutil.assigned_paths(stmt.target), env)
            return env
        if isinstance(stmt, ast.Delete):
            for target in stmt.targets:
                self.clear(astutil.assigned_paths(target), env)
            return env
        # Expr, Return, Assert, Raise, ... : reads + possible donations
        self.check_reads(stmt, env)
        if not isinstance(stmt, (ast.Return, ast.Raise)):
            # a donation inside `return f(num, den)` cannot be read
            # later on this path
            self.activate(stmt, env)
        return env


def check(src: SourceFile) -> Iterator[Finding]:
    table = _file_donating_map(src.tree)
    scopes = [src.tree.body]
    scopes.extend(fn.body for fn in astutil.functions(src.tree))
    seen: set[tuple[int, str]] = set()
    for body in scopes:
        flow = _Flow(table)
        flow.block(body, {})
        for line, path, callee, cline in sorted(flow.hits):
            if (line, path) in seen:
                continue
            seen.add((line, path))
            yield Finding(
                file=src.relpath, line=line, rule=RULE_ID,
                severity="error",
                message=(f"`{path}` was donated to `{callee}` on line "
                         f"{cline} and is read again before rebinding; "
                         f"donated buffers are consumed — carry the "
                         f"returned value forward instead"))
