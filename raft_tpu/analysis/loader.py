"""Module loader: parse the whole ``raft_tpu`` tree into one program.

A :class:`Program` is the unit every pass runs over: each ``*.py``
file under the analyzed packages parsed into an ``ast.Module`` with
its repo-relative path, dotted module name and source lines kept
alongside, plus the per-module symbol table (what every imported name
resolves to) the call-graph builder consumes.

Stdlib-only by design — the tools load this package without importing
``raft_tpu`` (no jax needed), so the gate runs on any checkout.
"""

from __future__ import annotations

import ast
import dataclasses
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

#: packages walked by default (repo-relative)
DEFAULT_PACKAGES: Tuple[str, ...] = ("raft_tpu",)


@dataclasses.dataclass
class ModuleInfo:
    """One parsed source file."""
    rel: str                 # repo-relative posix path
    name: str                # dotted module name ("raft_tpu.core.env")
    path: str                # absolute path
    tree: ast.Module
    source: str

    #: import symbol table: local name → dotted target. ``import x.y``
    #: binds "x" → "x"; ``import x.y as z`` binds "z" → "x.y";
    #: ``from x import y as w`` binds "w" → "x.y".
    symbols: Dict[str, str] = dataclasses.field(default_factory=dict)

    def __repr__(self) -> str:  # keep pytest diffs readable
        return f"ModuleInfo({self.rel})"


@dataclasses.dataclass
class Program:
    """Every parsed module, indexed both ways."""
    root: str
    modules: Dict[str, ModuleInfo]      # dotted name → info
    by_rel: Dict[str, ModuleInfo]       # repo-relative path → info

    def __iter__(self) -> Iterator[ModuleInfo]:
        return iter(self.modules.values())

    def get(self, name: str) -> Optional[ModuleInfo]:
        return self.modules.get(name)

    def rel(self, rel: str) -> Optional[ModuleInfo]:
        return self.by_rel.get(rel)


def _module_name(rel: str) -> str:
    parts = rel[:-3].split("/")          # strip .py
    if parts[-1] == "__init__":
        parts = parts[:-1]
    return ".".join(parts)


def _symbol_table(tree: ast.Module, modname: str) -> Dict[str, str]:
    """Local name → dotted target for every import in the module
    (module-level and nested — deferred imports inside functions are
    how this tree breaks cycles, so they resolve too)."""
    symbols: Dict[str, str] = {}
    pkg_parts = modname.split(".")
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                if a.asname:
                    symbols[a.asname] = a.name
                else:
                    symbols[a.name.split(".")[0]] = a.name.split(".")[0]
        elif isinstance(node, ast.ImportFrom):
            if node.level:  # relative import → absolute
                base = pkg_parts[:len(pkg_parts) - node.level]
                mod = ".".join(base + ([node.module]
                                       if node.module else []))
            else:
                mod = node.module or ""
            for a in node.names:
                if a.name == "*":
                    continue
                symbols[a.asname or a.name] = (f"{mod}.{a.name}"
                                               if mod else a.name)
    return symbols


def load_program(root: str,
                 packages: Sequence[str] = DEFAULT_PACKAGES) -> Program:
    """Parse every ``*.py`` under ``packages`` into a :class:`Program`.
    Unparseable files raise — a syntax error anywhere in the tree is
    itself a finding-worthy failure, surfaced loudly rather than
    skipped."""
    modules: Dict[str, ModuleInfo] = {}
    by_rel: Dict[str, ModuleInfo] = {}

    def _add(rel: str) -> None:
        path = os.path.join(root, rel)
        with open(path, encoding="utf-8") as f:
            source = f.read()
        tree = ast.parse(source, filename=rel)
        name = _module_name(rel)
        info = ModuleInfo(rel=rel, name=name, path=path, tree=tree,
                          source=source,
                          symbols=_symbol_table(tree, name))
        modules[name] = info
        by_rel[rel] = info

    for pkg in packages:
        pkg_dir = os.path.join(root, pkg)
        if not os.path.isdir(pkg_dir):
            continue
        for dirpath, dirnames, filenames in os.walk(pkg_dir):
            dirnames[:] = sorted(d for d in dirnames
                                 if not d.startswith((".", "__pycache"))
                                 )
            for fn in sorted(filenames):
                if not fn.endswith(".py"):
                    continue
                rel = os.path.relpath(os.path.join(dirpath, fn),
                                      root).replace(os.sep, "/")
                _add(rel)
    return Program(root=root, modules=modules, by_rel=by_rel)


# ---------------------------------------------------------------- scans
def iter_functions(info: ModuleInfo
                   ) -> Iterator[Tuple[str, ast.AST]]:
    """Yield ``(qualname, def-node)`` for every function in the module,
    methods and nested defs included. Qualnames are
    ``"pkg.mod:Outer.inner"`` — the ``:`` separates module from the
    in-module path so passes can split unambiguously."""
    def _walk(node: ast.AST, prefix: str) -> Iterator[Tuple[str, ast.AST]]:
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef)):
                q = f"{prefix}.{child.name}" if prefix else child.name
                yield f"{info.name}:{q}", child
                yield from _walk(child, q)
            elif isinstance(child, ast.ClassDef):
                q = (f"{prefix}.{child.name}" if prefix
                     else child.name)
                yield from _walk(child, q)
            else:
                yield from _walk(child, prefix)
    yield from _walk(info.tree, "")


def dotted(node: ast.expr) -> Optional[str]:
    """``a.b.c`` attribute/name chain → ``"a.b.c"`` (None when the
    chain bottoms out in a call/subscript — dynamic, unresolvable)."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def string_constants(tree: ast.AST) -> Iterator[Tuple[str, int]]:
    """Every string constant with its line (f-string parts included)."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            yield node.value, getattr(node, "lineno", 0)
