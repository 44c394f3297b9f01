"""Pass registry + the finding/severity model.

A pass is a callable ``run(program, graph) -> List[Finding]`` with a
``name``; :func:`run_passes` builds the program + call graph once and
feeds every requested pass. Findings carry a line for humans and a
line-independent *fingerprint* for the baseline file — a suppressed
finding stays suppressed across unrelated edits, and a genuinely new
one fails the gate.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence

from .callgraph import CallGraph, build_call_graph
from .loader import Program, load_program

ERROR, WARNING = "error", "warning"


@dataclasses.dataclass
class Finding:
    """One diagnostic. ``rule`` is the stable machine name
    (``host-sync-item``, ``lock-order-inversion``, …); ``where`` is the
    stable location token (function qualname, registry key, …) the
    fingerprint uses INSTEAD of the line number."""
    pass_name: str
    rule: str
    rel: str              # repo-relative file
    line: int
    message: str
    where: str = ""       # qualname / key — line-independent anchor
    severity: str = ERROR

    @property
    def fingerprint(self) -> str:
        return f"{self.pass_name}:{self.rule}:{self.rel}:{self.where}"

    def render(self) -> str:
        sev = "" if self.severity == ERROR else f" [{self.severity}]"
        return (f"{self.rel}:{self.line}: {self.rule}{sev}: "
                f"{self.message}")


class AnalysisPass:
    """Base class: subclasses set ``name`` and implement ``run``."""
    name = "?"

    def run(self, program: Program, graph: CallGraph) -> List[Finding]:
        raise NotImplementedError

    def finding(self, rule: str, rel: str, line: int, message: str,
                where: str = "", severity: str = ERROR) -> Finding:
        return Finding(pass_name=self.name, rule=rule, rel=rel,
                       line=line, message=message, where=where,
                       severity=severity)


_PASSES: Dict[str, Callable[[], AnalysisPass]] = {}


def register_pass(factory: Callable[[], AnalysisPass]) -> None:
    _PASSES[factory().name] = factory


def all_passes() -> List[str]:
    _ensure_registered()
    return sorted(_PASSES)


def _ensure_registered() -> None:
    # the flagship passes self-register on import; imported here (not
    # at module top) so framework ↔ pass modules stay cycle-free.
    # Unconditional: a partial registry (e.g. only the registry pass,
    # pulled in by the package __init__) must still complete
    from . import locks, purity, registry  # noqa: F401


def run_passes(root: str, names: Optional[Sequence[str]] = None,
               program: Optional[Program] = None,
               graph: Optional[CallGraph] = None
               ) -> Dict[str, List[Finding]]:
    """Run the named passes (default: all) over ``root``. Returns
    pass name → findings, deterministically ordered."""
    _ensure_registered()
    if program is None:
        from .registry import SCAN_PACKAGES
        program = load_program(root, packages=SCAN_PACKAGES)
    if graph is None:
        graph = build_call_graph(program)
    out: Dict[str, List[Finding]] = {}
    for name in (names if names is not None else all_passes()):
        if name not in _PASSES:
            raise KeyError(f"graftlint: unknown pass {name!r} "
                           f"(have: {', '.join(all_passes())})")
        findings = sorted(_PASSES[name]().run(program, graph),
                          key=lambda f: (f.rel, f.line, f.rule,
                                         f.where))
        # one finding per fingerprint: several call sites can reach
        # the same hazard; the baseline suppresses them as one
        seen, unique = set(), []
        for f in findings:
            if f.fingerprint not in seen:
                seen.add(f.fingerprint)
                unique.append(f)
        out[name] = unique
    return out
