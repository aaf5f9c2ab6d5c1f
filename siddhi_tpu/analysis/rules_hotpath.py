"""R5 — no host pull in hot-path step code.

Inside a jit-compiled step, a ``float()``/``int()``/``bool()``/
``.item()``/``np.asarray()`` on a traced value either fails at trace
time or — worse, on concrete leaves that escaped tracing — forces a
synchronous device->host transfer per batch, the exact per-pull
round trip the CompletionPump exists to amortize. The rule scans
``core/query``, ``core/join`` and ``parallel`` for functions that are
jit-compiled (decorated with ``jax.jit``/``partial(jax.jit, ...)``,
passed to a ``jax.jit(...)`` call in the same scope, or named like a
step kernel) and flags host-pull calls in their bodies.

Shape arithmetic is exempt: ``int(x.shape[0])`` and friends are static
under jit and idiomatic.
"""

from __future__ import annotations

import ast
from typing import List, Set

from siddhi_tpu.analysis.engine import Finding, LintContext, Rule

_HOT_DIRS = ("core/query/", "core/join/", "parallel/")
# the codebase's convention for traced kernels built by closures: a
# NESTED def named `step`/`fn`/`kernel` inside a builder is the body
# that jax.jit traces (build_step_fn / build_side_step_fn / _make_step)
_KERNEL_NAMES = ("step", "fn", "kernel", "fused", "sharded", "one_dev")
_PULL_BUILTINS = ("float", "int", "bool")
_STATIC_ATTRS = ("shape", "ndim", "size", "dtype", "itemsize", "nbytes")


def _is_jit_call(node: ast.Call) -> bool:
    """``jax.jit(...)`` / ``jit(...)`` / ``partial(jax.jit, ...)``."""
    fn = node.func
    if isinstance(fn, ast.Attribute) and fn.attr == "jit":
        return True
    if isinstance(fn, ast.Name) and fn.id == "jit":
        return True
    if isinstance(fn, ast.Name) and fn.id == "partial" and node.args:
        first = node.args[0]
        return (isinstance(first, (ast.Attribute, ast.Name))
                and getattr(first, "attr", getattr(first, "id", None))
                == "jit")
    return False


def _jitted_names(tree: ast.AST) -> Set[str]:
    """Function names referenced as the first argument of a jit call
    anywhere in the module (``jax.jit(fn, donate_argnums=0)``)."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _is_jit_call(node) and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Name):
                names.add(arg.id)
    return names


def _is_static_arg(node: ast.AST) -> bool:
    """True when the expression is shape/metadata arithmetic — static
    under jit, never a device pull."""
    if isinstance(node, ast.Constant):
        return True
    for sub in ast.walk(node):
        if isinstance(sub, ast.Attribute) and sub.attr in _STATIC_ATTRS:
            return True
        if (isinstance(sub, ast.Call) and isinstance(sub.func, ast.Name)
                and sub.func.id == "len"):
            return True
    return False


class HostPullRule(Rule):
    id = "R5"
    title = "no host pull in hot-path step code"

    def run(self, ctx: LintContext) -> List[Finding]:
        findings: List[Finding] = []
        for mod in ctx.modules:
            if not any(d in mod.path for d in _HOT_DIRS):
                continue
            jitted = _jitted_names(mod.tree)
            # nested = defined inside another function (a builder)
            nested: Set[int] = set()
            for node in ast.walk(mod.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    for sub in ast.walk(node):
                        if sub is not node and isinstance(
                                sub, (ast.FunctionDef, ast.AsyncFunctionDef)):
                            nested.add(id(sub))
            for node in ast.walk(mod.tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                if not self._is_step_fn(node, jitted,
                                        id(node) in nested):
                    continue
                self._scan_step(mod, node, findings)
        return findings

    def _is_step_fn(self, node, jitted: Set[str], is_nested: bool) -> bool:
        if node.name in jitted:
            return True
        if is_nested and node.name in _KERNEL_NAMES:
            return True
        for dec in node.decorator_list:
            if isinstance(dec, ast.Call) and _is_jit_call(dec):
                return True
            if (isinstance(dec, ast.Attribute) and dec.attr == "jit") or \
                    (isinstance(dec, ast.Name) and dec.id == "jit"):
                return True
        return False

    def _scan_step(self, mod, func, findings) -> None:
        # the candidate's OWN body only: nested defs are host-side
        # helpers or separate candidates in their own right
        todo = list(ast.iter_child_nodes(func))
        body: list = []
        while todo:
            n = todo.pop()
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            body.append(n)
            todo.extend(ast.iter_child_nodes(n))
        for node in body:
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if isinstance(fn, ast.Name) and fn.id in _PULL_BUILTINS:
                if node.args and not _is_static_arg(node.args[0]):
                    findings.append(Finding(
                        self.id, mod.path, node.lineno,
                        f"{fn.id}() on a device value inside step "
                        f"'{func.name}' forces a synchronous host pull "
                        f"— keep the value on device or ride it in the "
                        f"packed __meta__"))
            elif isinstance(fn, ast.Attribute):
                if fn.attr == "item":
                    findings.append(Finding(
                        self.id, mod.path, node.lineno,
                        f".item() inside step '{func.name}' is a "
                        f"synchronous host pull — batch it through the "
                        f"meta/device_get path"))
                elif (fn.attr in ("asarray", "array")
                      and isinstance(fn.value, ast.Name)
                      and fn.value.id in ("np", "numpy")):
                    findings.append(Finding(
                        self.id, mod.path, node.lineno,
                        f"np.{fn.attr}() inside step '{func.name}' "
                        f"pulls to host — step code must stay on "
                        f"device (use jnp, or hoist the host work out "
                        f"of the step)"))
