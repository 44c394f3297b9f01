"""Registry-derivation pass: site registries derived *from source*.

Six hand-pinned tables used to define what a "site" is
(``check_instrumented``'s HOT_PATHS/FAULT_SITES/EVENT_SITES/…,
``faults.KNOWN_SITES``, ``flight.KNOWN_EVENT_KINDS``, the README
env-knob table). Every new subsystem grew them by hand — and could
ship half-registered. This pass derives the ground truth from the
AST and diffs it against every declared registry, in BOTH directions:

- ``unregistered-fault-site`` / ``orphan-fault-site`` — a
  ``fault_point("x")`` call whose site is missing from
  ``faults.KNOWN_SITES``, and a KNOWN_SITES entry no code ever arms;
- ``unknown-event-kind`` / ``orphan-event-kind`` — a timeline emitter
  recording a kind outside ``flight.KNOWN_EVENT_KINDS``, and a
  vocabulary kind no emitter produces;
- ``unregistered-hot-path`` — an ``@instrument``-decorated module
  function absent from ``check_instrumented.HOT_PATHS`` (the
  half-registered-subsystem bug, caught statically);
- ``unregistered-quality-site`` — a module calling the quality
  recorders with no QUALITY_SITES entry;
- ``unregistered-env-knob`` / ``undocumented-env-knob`` /
  ``stale-readme-knob`` — the code ⊆ ``core/env.KNOBS`` ⊆ README
  chain for every ``RAFT_TPU_*`` knob.

``tools/check_instrumented.py`` *imports* the derived registries from
here (``derive_registries``) instead of redeclaring them, so the two
tools can never disagree about what a site is (equality pinned by
tests/test_analysis.py).
"""

from __future__ import annotations

import ast
import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Set, Tuple

from .callgraph import CallGraph
from .framework import AnalysisPass, Finding, WARNING, register_pass
from .loader import (ModuleInfo, Program, dotted, load_program,
                     string_constants)

#: what the program spans beyond raft_tpu/ — the tools and the
#: attribution scripts carry registry tables and env knobs of their own
SCAN_PACKAGES: Tuple[str, ...] = ("raft_tpu", "tools", "benchmarks")

FAULTS_MODULE = "raft_tpu/resilience/faults.py"
FLIGHT_MODULE = "raft_tpu/observability/flight.py"
TIMELINE_MODULE = "raft_tpu/observability/timeline.py"
QUALITY_MODULE = "raft_tpu/observability/quality.py"
ENV_MODULE = "raft_tpu/core/env.py"
CHECKER_MODULE = "tools/check_instrumented.py"
README = "README.md"

_KNOB_RE = re.compile(r"^RAFT_TPU_[A-Z0-9_]+$")
_README_KNOB_RE = re.compile(r"`(RAFT_TPU_[A-Z0-9_]+)")

#: emitters whose defining module is NOT timeline.py, mapped to the
#: flight event kind they (transitively) produce. The single curated
#: seam left: these are bridges (decorator → span, fault_point →
#: fault, quality recorders → quality) whose kind cannot be read off
#: a ``rec.record("<kind>", ...)`` literal in timeline.py.
ALIAS_EMITTERS: Dict[str, str] = {
    "instrument": "span",
    "span": "span",
    "fault_point": "fault",
    "record_collective": "collective",
    "record_drift": "drift",
    "record_certificate": "quality",
    "record_pending": "quality",
    "record_pq_rungs": "quality",
}

QUALITY_RECORDERS = ("record_certificate", "record_pending",
                    "record_pq_rungs", "ShadowSampler")


# ---------------------------------------------------------------- utils
def module_literal(info: Optional[ModuleInfo], name: str):
    """``ast.literal_eval`` of a module-level ``NAME = <literal>``
    assignment (AnnAssign included). None when absent/non-literal."""
    if info is None:
        return None
    for node in info.tree.body:
        targets: List[str] = []
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) \
                and isinstance(node.target, ast.Name):
            targets = [node.target.id]
        if name in targets and getattr(node, "value", None) is not None:
            try:
                return ast.literal_eval(node.value)
            except ValueError:
                return None
    return None


def referenced_names(tree: ast.AST) -> Set[str]:
    """Plain names + attribute names + from-import names — the ONE
    definition of "module references emitter X" shared with
    check_instrumented."""
    names: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.ImportFrom):
            names.update(a.name for a in node.names)
    return names


def _call_name(node: ast.Call) -> Optional[str]:
    f = node.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return None


# ------------------------------------------------------- derivations
def derive_fault_sites(program: Program) -> Dict[str, Tuple[str, ...]]:
    """module rel → literal sites armed via ``fault_point("<site>")``
    (the defining module excluded — its internal calls are plumbing)."""
    out: Dict[str, Tuple[str, ...]] = {}
    for info in program:
        if info.rel == FAULTS_MODULE \
                or not info.rel.startswith("raft_tpu/"):
            continue
        sites: Set[str] = set()
        for node in ast.walk(info.tree):
            if isinstance(node, ast.Call) and node.args \
                    and _call_name(node) == "fault_point" \
                    and isinstance(node.args[0], ast.Constant) \
                    and isinstance(node.args[0].value, str):
                sites.add(node.args[0].value)
        if sites:
            out[info.rel] = tuple(sorted(sites))
    return out


def parse_known_sites(program: Program) -> Optional[Dict[str, tuple]]:
    return module_literal(program.rel(FAULTS_MODULE), "KNOWN_SITES")


def parse_known_event_kinds(program: Program) -> Optional[Set[str]]:
    val = module_literal(program.rel(FLIGHT_MODULE),
                         "KNOWN_EVENT_KINDS")
    return {str(v) for v in val} if val is not None else None


def derive_emitter_kinds(program: Program) -> Dict[str, str]:
    """emitter name → flight kind: every top-level ``emit_*`` /
    ``record_*`` def in timeline.py whose body records a literal kind,
    plus the curated :data:`ALIAS_EMITTERS` bridges."""
    out = dict(ALIAS_EMITTERS)
    info = program.rel(TIMELINE_MODULE)
    if info is None:
        return out
    for node in info.tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if not node.name.startswith(("emit_", "record_")):
            continue
        for sub in ast.walk(node):
            if isinstance(sub, ast.Call) \
                    and _call_name(sub) == "record" and sub.args \
                    and isinstance(sub.args[0], ast.Constant) \
                    and isinstance(sub.args[0].value, str):
                out[node.name] = sub.args[0].value
                break
    return out


def derive_event_sites(program: Program,
                       emitters: Optional[Dict[str, str]] = None
                       ) -> Dict[str, Tuple[str, ...]]:
    """module rel → timeline emitters the module references (names ∩
    known emitters), for every raft_tpu/ module. This IS the event-
    site registry — check_instrumented's policy checks run on top."""
    emitters = (derive_emitter_kinds(program) if emitters is None
                else emitters)
    out: Dict[str, Tuple[str, ...]] = {}
    for info in program:
        if not info.rel.startswith("raft_tpu/"):
            continue
        if info.rel in (TIMELINE_MODULE, FLIGHT_MODULE):
            continue   # the vocabulary itself, not an emitting site
        refs = referenced_names(info.tree) & set(emitters)
        if refs:
            out[info.rel] = tuple(sorted(refs))
    return out


def derive_instrumented(program: Program) -> Dict[str, Tuple[str, ...]]:
    """module rel → module-level functions decorated ``@instrument``
    (bare, called, or attribute spelling)."""
    def _is_instrument(dec: ast.expr) -> bool:
        if isinstance(dec, ast.Call):
            dec = dec.func
        name = dotted(dec)
        return name is not None \
            and name.rsplit(".", 1)[-1] == "instrument"

    out: Dict[str, Tuple[str, ...]] = {}
    for info in program:
        if not info.rel.startswith("raft_tpu/"):
            continue
        funcs = tuple(sorted(
            n.name for n in info.tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            and any(_is_instrument(d) for d in n.decorator_list)))
        if funcs:
            out[info.rel] = funcs
    return out


def derive_quality_sites(program: Program) -> Dict[str, Tuple[str, ...]]:
    """module rel → quality recorders referenced (defining module
    excluded)."""
    out: Dict[str, Tuple[str, ...]] = {}
    for info in program:
        if not info.rel.startswith("raft_tpu/") \
                or info.rel == QUALITY_MODULE \
                or info.rel.endswith("__init__.py"):
            continue   # package __init__ re-exports record nothing
        refs = referenced_names(info.tree) & set(QUALITY_RECORDERS)
        if refs:
            out[info.rel] = tuple(sorted(refs))
    return out


def derive_env_knobs(program: Program) -> Dict[str, Set[str]]:
    """knob name → module rels whose source carries the bare literal
    (the registry module itself excluded — it IS the declaration)."""
    out: Dict[str, Set[str]] = {}
    for info in program:
        if info.rel == ENV_MODULE:
            continue
        for value, _line in string_constants(info.tree):
            if _KNOB_RE.match(value):
                out.setdefault(value, set()).add(info.rel)
    return out


def parse_env_registry(program: Program) -> Optional[Set[str]]:
    """Knob names declared in ``core/env.py``: first argument of every
    ``_knob(...)`` / ``Knob(...)`` call. None when the module is
    missing (pre-registry tree)."""
    info = program.rel(ENV_MODULE)
    if info is None:
        return None
    names: Set[str] = set()
    for node in ast.walk(info.tree):
        if isinstance(node, ast.Call) and node.args \
                and _call_name(node) in ("_knob", "Knob") \
                and isinstance(node.args[0], ast.Constant) \
                and isinstance(node.args[0].value, str):
            names.add(node.args[0].value)
    return names or None


def parse_readme_knobs(root: str) -> Optional[Set[str]]:
    import os
    path = os.path.join(root, README)
    if not os.path.exists(path):
        return None
    with open(path, encoding="utf-8") as f:
        text = f.read()
    marker = "## Environment knobs"
    start = text.find(marker)
    if start < 0:
        return None
    end = text.find("\n## ", start + len(marker))
    section = text[start:end if end > 0 else len(text)]
    return set(_README_KNOB_RE.findall(section))


@dataclasses.dataclass
class Registries:
    """Everything derived from source in one pass — what
    check_instrumented imports instead of redeclaring."""
    fault_sites: Dict[str, Tuple[str, ...]]
    known_sites: Optional[Dict[str, tuple]]
    emitter_kinds: Dict[str, str]
    event_sites: Dict[str, Tuple[str, ...]]
    known_event_kinds: Optional[Set[str]]
    instrumented: Dict[str, Tuple[str, ...]]
    quality_sites: Dict[str, Tuple[str, ...]]
    env_knobs: Dict[str, Set[str]]
    env_registry: Optional[Set[str]]
    readme_knobs: Optional[Set[str]]


def derive_registries(root: str,
                      program: Optional[Program] = None) -> Registries:
    if program is None:
        program = load_program(root, packages=SCAN_PACKAGES)
    emitters = derive_emitter_kinds(program)
    return Registries(
        fault_sites=derive_fault_sites(program),
        known_sites=parse_known_sites(program),
        emitter_kinds=emitters,
        event_sites=derive_event_sites(program, emitters),
        known_event_kinds=parse_known_event_kinds(program),
        instrumented=derive_instrumented(program),
        quality_sites=derive_quality_sites(program),
        env_knobs=derive_env_knobs(program),
        env_registry=parse_env_registry(program),
        readme_knobs=parse_readme_knobs(root),
    )


# --------------------------------------------------------------- pass
class RegistryPass(AnalysisPass):
    name = "registry"

    def run(self, program: Program, graph: CallGraph) -> List[Finding]:
        del graph
        regs = derive_registries(program.root, program=program)
        findings: List[Finding] = []

        # -- fault sites ⊆ KNOWN_SITES ⊆ fault sites ------------------
        if regs.known_sites is None:
            findings.append(self.finding(
                "missing-registry", FAULTS_MODULE, 1,
                "faults.KNOWN_SITES dict literal not found — the "
                "fault-site registry is gone", where="KNOWN_SITES"))
        else:
            used: Dict[str, str] = {}
            for rel, sites in sorted(regs.fault_sites.items()):
                for site in sites:
                    used.setdefault(site, rel)
                    if site not in regs.known_sites:
                        findings.append(self.finding(
                            "unregistered-fault-site", rel, 1,
                            f"fault_point({site!r}) is armed here but "
                            f"{site!r} is not in faults.KNOWN_SITES — "
                            f"the injection matrix would never test "
                            f"it", where=f"{site}@{rel}"))
            for site in sorted(set(regs.known_sites) - set(used)):
                findings.append(self.finding(
                    "orphan-fault-site", FAULTS_MODULE, 1,
                    f"faults.KNOWN_SITES[{site!r}] is registered but "
                    f"no module arms fault_point({site!r}) — dead "
                    f"registry entry", where=site))

        # -- emitter kinds ⊆ KNOWN_EVENT_KINDS ⊆ emitter kinds --------
        if regs.known_event_kinds is None:
            findings.append(self.finding(
                "missing-registry", FLIGHT_MODULE, 1,
                "flight.KNOWN_EVENT_KINDS tuple not found — the "
                "event vocabulary is gone", where="KNOWN_EVENT_KINDS"))
        else:
            for emitter, kind in sorted(regs.emitter_kinds.items()):
                if kind not in regs.known_event_kinds:
                    findings.append(self.finding(
                        "unknown-event-kind", TIMELINE_MODULE, 1,
                        f"emitter {emitter}() records kind {kind!r} "
                        f"which is not in flight.KNOWN_EVENT_KINDS",
                        where=f"{emitter}:{kind}"))
            produced = set(regs.emitter_kinds.values())
            for kind in sorted(regs.known_event_kinds - produced):
                findings.append(self.finding(
                    "orphan-event-kind", FLIGHT_MODULE, 1,
                    f"KNOWN_EVENT_KINDS kind {kind!r} has no emitter "
                    f"in timeline.py — vocabulary entry nothing can "
                    f"produce", where=kind))

        # -- instrumented functions registered as hot paths ----------
        checker = program.rel(CHECKER_MODULE)
        hot_paths = module_literal(checker, "HOT_PATHS")
        if hot_paths is None:
            findings.append(self.finding(
                "missing-registry", CHECKER_MODULE, 1,
                "check_instrumented.HOT_PATHS dict literal not found",
                where="HOT_PATHS"))
        else:
            for rel, funcs in sorted(regs.instrumented.items()):
                missing = set(funcs) - set(hot_paths.get(rel, ()))
                for fn in sorted(missing):
                    findings.append(self.finding(
                        "unregistered-hot-path", rel, 1,
                        f"{fn}() is @instrument-decorated but absent "
                        f"from check_instrumented.HOT_PATHS[{rel!r}] "
                        f"— it would ship outside the tier-1 "
                        f"instrumentation gate", where=f"{rel}:{fn}"))

        # -- quality recorders registered ----------------------------
        quality_sites = module_literal(checker, "QUALITY_SITES") or {}
        for rel, refs in sorted(regs.quality_sites.items()):
            if rel not in quality_sites:
                findings.append(self.finding(
                    "unregistered-quality-site", rel, 1,
                    f"module references quality recorders "
                    f"({', '.join(refs)}) but has no "
                    f"check_instrumented.QUALITY_SITES entry",
                    where=rel, severity=WARNING))

        # -- env knobs: code ⊆ registry ⊆ README ---------------------
        if regs.env_registry is None:
            findings.append(self.finding(
                "missing-registry", ENV_MODULE, 1,
                "core/env.py knob registry not found — every "
                "RAFT_TPU_* knob must be declared there",
                where="KNOBS"))
        else:
            for knob in sorted(regs.env_knobs):
                if knob not in regs.env_registry:
                    rels = sorted(regs.env_knobs[knob])
                    findings.append(self.finding(
                        "unregistered-env-knob", rels[0], 1,
                        f"{knob} is read in code ({', '.join(rels)}) "
                        f"but not declared in core/env.KNOBS",
                        where=knob))
            if regs.readme_knobs is None:
                findings.append(self.finding(
                    "missing-registry", README, 1,
                    "README '## Environment knobs' table not found",
                    where="readme-knobs"))
            else:
                for knob in sorted(regs.env_registry
                                   - regs.readme_knobs):
                    findings.append(self.finding(
                        "undocumented-env-knob", ENV_MODULE, 1,
                        f"{knob} is declared in core/env.KNOBS but "
                        f"missing from the README env-knob table",
                        where=knob))
                for knob in sorted(regs.readme_knobs
                                   - regs.env_registry):
                    findings.append(self.finding(
                        "stale-readme-knob", README, 1,
                        f"README documents {knob} but core/env.KNOBS "
                        f"does not declare it — stale or misspelled "
                        f"row", where=knob))
                for knob in sorted(regs.env_registry
                                   - set(regs.env_knobs)):
                    findings.append(self.finding(
                        "orphan-env-knob", ENV_MODULE, 1,
                        f"{knob} is declared in core/env.KNOBS but "
                        f"never read anywhere in code",
                        where=knob, severity=WARNING))
        return findings


register_pass(RegistryPass)
