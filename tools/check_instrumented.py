#!/usr/bin/env python
"""Static check: every hot-path primitive carries @instrument, and the
cost-capture sites feed the roofline profiler.

Pure-AST, no TPU (and no raft_tpu import) needed, so it runs anywhere —
it is wired into the tier-1 suite via tests/test_observability.py. The
check asserts:

1. per module in :data:`HOT_PATHS`: the module imports ``instrument``
   from ``raft_tpu.observability``, and each listed function is
   decorated with it (bare ``@instrument`` or ``@instrument(...)``,
   plain name or attribute spelling);
2. per module in :data:`COST_CAPTURE_SITES`: the module calls the named
   profiler capture method — the static guarantee that everything the
   hot-path list reports (AOT runtime entries via ``_aot_call``,
   benchmark measurements via ``Fixture.run``) also flows through XLA
   cost capture, so ``roofline_report()`` can attribute it. Removing a
   capture call silently reverts BENCH artifacts to seconds-only — the
   exact evidence regression this gate exists to catch.

Extend HOT_PATHS when a new primitive ships — forgetting to is exactly
the regression this check exists to catch: a hot path that silently
ships unobserved.

Since ISSUE 13, the MIRROR tables (FAULT_SITES, EMITTER_KINDS) are no
longer hand-pinned: they are DERIVED from source by
``raft_tpu.analysis.registry`` (graftlint's registry pass) and imported
here, so this tool and graftlint can never disagree about what a
"site" is — equality is pinned by tests/test_analysis.py. The curated
tables that remain (HOT_PATHS, COST_CAPTURE_SITES, EVENT_SITES,
QUALITY_SITES, KERNEL_VARIANTS) are *policy* — what MUST be covered —
and graftlint diffs them against the derived ground truth in the
reverse direction (an @instrument function missing from HOT_PATHS is
a lint error).

Usage: ``python tools/check_instrumented.py`` (exit 0 = clean).
"""

from __future__ import annotations

import ast
import os
import sys
from typing import Dict, List, Optional, Sequence, Tuple

try:                      # imported as tools.check_instrumented
    from tools.graftlint import load_analysis
except ImportError:       # imported with tools/ on sys.path
    from graftlint import load_analysis

# module (repo-relative) → functions that must be instrumented
HOT_PATHS: Dict[str, Sequence[str]] = {
    "raft_tpu/matrix/select_k.py": ("select_k",),
    "raft_tpu/matrix/select_k_chunked.py": ("select_k_chunked",),
    "raft_tpu/matrix/select_k_slotted.py": ("select_k_slotted",),
    "raft_tpu/distance/pairwise.py": ("pairwise_distance",),
    "raft_tpu/distance/fused_l2nn.py": (
        "fused_l2_nn_argmin", "knn", "knn_sharded"),
    "raft_tpu/distance/knn_fused.py": ("knn_fused",
                                       "prepare_knn_index"),
    "raft_tpu/sparse/tiled.py": ("tile_csr", "tile_csr_pairs"),
    "raft_tpu/sparse/sharded.py": ("spmv_sharded", "spmm_sharded"),
    "raft_tpu/solver/linear_assignment.py": ("solve_lap",),
    "raft_tpu/tune/fused.py": ("autotune_fused",),
    "raft_tpu/tune/sharded.py": ("autotune_sharded",),
    "raft_tpu/tune/ivf.py": ("autotune_fine_scan",
                             "autotune_pq_scan"),
    "raft_tpu/distance/knn_sharded.py": ("knn_fused_sharded",
                                         "prepare_knn_index_sharded"),
    "raft_tpu/serving/engine.py": ("execute_batch",),
    "raft_tpu/serving/snapshot.py": ("build_snapshot",),
    "raft_tpu/cluster/kmeans.py": ("kmeans_fit", "kmeans_predict"),
    "raft_tpu/ann/ivf_flat.py": ("build_ivf_flat", "search_ivf_flat"),
    "raft_tpu/ann/ivf_pq.py": ("build_ivf_pq", "search_ivf_pq"),
    "raft_tpu/mutable/index.py": ("apply_upsert", "apply_delete",
                                  "search_view"),
}

# module (repo-relative) → profiler capture methods it must call
# (attribute calls, e.g. ``res.profiler.capture(...)``)
COST_CAPTURE_SITES: Dict[str, Sequence[str]] = {
    "raft_tpu/runtime/entry_points.py": ("capture",),
    "raft_tpu/benchmark.py": ("capture_fn",),
    "raft_tpu/tune/fused.py": ("capture_fn",),
    "raft_tpu/tune/sharded.py": ("capture_fn",),
    # the ANN tier's hot kernels: the k-means assignment tile and the
    # IVF fine scan both feed the roofline profiler, so BENCH_ANN
    # frontiers carry flops/bytes next to recall
    "raft_tpu/cluster/kmeans.py": ("capture_fn",),
    "raft_tpu/ann/ivf_flat.py": ("capture_fn",),
    # the PQ ADC table build — the per-chunk cost the compressed tier
    # adds on top of the shared fine-scan machinery
    "raft_tpu/ann/ivf_pq.py": ("capture_fn",),
    # the int8 quantize prep (prepare_knn_index db_dtype="int8")
    "raft_tpu/distance/knn_fused.py": ("capture_fn",),
}

# sharded-merge observability sites: the merge rounds must flow through
# the COUNTED comms surface (MeshComms methods that call _count), and
# comms.py must count the p2p/permute collectives under their own
# labels. A merge round rewritten onto raw jax.lax collectives would
# silently vanish from the metrics exporters — exactly the regression
# this table catches.
# module → attribute-call names it must contain
SHARDED_MERGE_SITES: Dict[str, Sequence[str]] = {
    "raft_tpu/distance/knn_sharded.py": ("collective_permute",
                                         "allgather"),
}
# comms.py must register these collective labels with _count(...)
COUNTED_COLLECTIVES = ("collective_permute", "device_send")

# module (repo-relative) → fault-injection sites it carries. DERIVED
# from source (every literal ``fault_point("<site>")`` call) by
# graftlint's registry derivation — this tool IMPORTS the ground truth
# instead of redeclaring it, so the two can never disagree about what
# a site is. The policy checks on top: every HOT_PATHS module must
# carry ≥ 1 site (check_fault_sites) and the derived site names must
# agree with faults.KNOWN_SITES in BOTH directions
# (check_fault_registry; also pinned at runtime by
# tests/test_resilience.py).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DERIVED = load_analysis(_REPO_ROOT).registry.derive_registries(
    _REPO_ROOT)

FAULT_SITES: Dict[str, Sequence[str]] = dict(_DERIVED.fault_sites)

# timeline-event gate: every hot-path module and every fault-site
# module must emit flight-recorder events — a hot path invisible in a
# Perfetto trace cannot be reconstructed post-mortem, which is exactly
# the regression this gate catches. A module "emits" by referencing at
# least the listed emitter callables (``@instrument``/``fault_point``
# route through the flight recorder; the ``emit_*`` helpers live in
# raft_tpu/observability/timeline.py). EMITTER_KINDS maps each emitter
# to the flight event kind it produces; the checker statically asserts
# every kind exists in flight.KNOWN_EVENT_KINDS (parsed from the
# source), and tests/test_flight.py pins the same fact at runtime.
# emitter → flight event kind. DERIVED: every ``emit_*``/``record_*``
# helper in observability/timeline.py paired with the literal kind its
# body records, plus analysis.registry.ALIAS_EMITTERS (the bridges —
# @instrument → span, fault_point → fault, quality recorders →
# quality — whose kind cannot be read off a timeline literal).
EMITTER_KINDS: Dict[str, str] = dict(_DERIVED.emitter_kinds)

EVENT_SITES: Dict[str, Sequence[str]] = {
    # every HOT_PATHS module: spans via @instrument + fault events
    **{rel: ("instrument", "fault_point") for rel in HOT_PATHS},
    # fault-site modules outside HOT_PATHS
    "raft_tpu/runtime/entry_points.py": (
        "fault_point", "emit_compile", "emit_dispatch"),
    "raft_tpu/sparse/plan_cache.py": ("fault_point",),
    "raft_tpu/comms/host_comms.py": ("fault_point",),
    # the emit wiring itself — deleting a bridge silently empties the
    # timeline even though every call site still "emits"
    "raft_tpu/comms/comms.py": ("record_collective",),
    "raft_tpu/resilience/faults.py": ("emit_fault",),
    "raft_tpu/resilience/policy.py": ("emit_retry",
                                      "emit_degradation"),
    "raft_tpu/resilience/deadline.py": ("emit_deadline",),
    "raft_tpu/core/interruptible.py": ("emit_deadline",),
    "raft_tpu/observability/spans.py": ("emit_span",),
    "raft_tpu/observability/hooks.py": ("emit_collective",
                                        "emit_compile",
                                        "emit_benchmark"),
    "raft_tpu/benchmark.py": ("record_drift",),
    # the serving engine: every module under raft_tpu/serving/ must
    # appear here (enforced structurally by check_serving_coverage) —
    # enqueue/flush/shed/swap/warmup all flow through emit_serving
    "raft_tpu/serving/engine.py": ("instrument", "fault_point",
                                   "emit_serving", "emit_flow"),
    "raft_tpu/serving/snapshot.py": ("instrument", "fault_point",
                                     "emit_serving"),
    "raft_tpu/serving/buckets.py": ("emit_marker",),
    # the ANN tier: per-iteration k-means markers, IVF build/search
    # markers (probed-bytes fraction rides the search event)
    "raft_tpu/cluster/kmeans.py": ("instrument", "fault_point",
                                   "emit_marker"),
    "raft_tpu/ann/ivf_flat.py": ("instrument", "fault_point",
                                 "emit_marker"),
    # the compressed tier: build/search markers (eq stats, schedule
    # picks, certificate fallbacks) ride next to the span/fault events
    "raft_tpu/ann/ivf_pq.py": ("instrument", "fault_point",
                               "emit_marker"),
    # the fine-scan/pq schedule autotuner (schema 5/6 columns)
    "raft_tpu/tune/ivf.py": ("instrument", "fault_point"),
    # the quantized index build: the quantize_index marker (per-build
    # Eq stats) rides next to the span + fault events
    "raft_tpu/distance/knn_fused.py": ("instrument", "fault_point",
                                       "emit_marker", "record_pending"),
    # the quality plane itself: its recorders must still route through
    # the flight emitter (deleting the bridge would silently empty the
    # quality timeline while every call site keeps "recording")
    "raft_tpu/observability/quality.py": ("emit_quality",),
    # the mutation plane: every write emits into the write-ahead
    # mutation stream, the layout prep marks its geometry, and the
    # delta-tail searches report certificate/fixup counters like every
    # other certified path
    "raft_tpu/mutable/index.py": ("instrument", "fault_point",
                                  "emit_mutation", "record_pending"),
    "raft_tpu/mutable/layout.py": ("emit_marker",),
    # the durability plane: WAL segment lifecycle rides markers,
    # checkpoint commits + recoveries ride the mutation stream — a
    # crash recovery invisible in the flight timeline cannot be
    # audited post-mortem
    "raft_tpu/mutable/wal.py": ("fault_point", "emit_marker"),
    "raft_tpu/mutable/checkpoint.py": ("fault_point", "emit_mutation"),
    # the telemetry front door (ISSUE 16): explain records land on the
    # flight timeline as "explain" events, SLO burn transitions as
    # "alert" events — deleting either bridge silently blinds the
    # debugz surfaces while every capture/tick keeps "running"
    "raft_tpu/observability/explain.py": ("emit_explain",),
    "raft_tpu/observability/slo.py": ("emit_alert",),
    # the forensics plane (ISSUE 17): the watchdog's stall detections
    # and the blackbox's clean-shutdown epilogue are themselves flight
    # events — a hang or a shutdown invisible in the timeline would
    # defeat the very postmortem this plane exists to serve
    "raft_tpu/observability/watchdog.py": ("emit_stall",),
    "raft_tpu/observability/blackbox.py": ("emit_epilogue",),
}

#: quality-telemetry gate (ISSUE 10): every module with a certificate /
#: fixup / rescore path must report into the quality plane — a
#: certified result path that silently stops counting its fixups is
#: exactly the evidence regression ROADMAP item 2 cannot afford (the
#: measured TPU fixup rate decides per-query Eq tightening). Each
#: module must reference the listed observability.quality recorders.
QUALITY_SITES: Dict[str, Sequence[str]] = {
    "raft_tpu/distance/knn_fused.py": ("record_pending",),
    "raft_tpu/distance/knn_sharded.py": ("record_pending",),
    "raft_tpu/ann/ivf_flat.py": ("record_certificate",
                                 "record_pending"),
    # the PQ tier's ADC scan reports its certificate/rerun counters
    # at the host sync its rerun decision already pays, plus the
    # per-rung ladder outcomes (certified / widened / exact_rerun)
    "raft_tpu/ann/ivf_pq.py": ("record_certificate",
                               "record_pq_rungs"),
    "raft_tpu/runtime/entry_points.py": ("record_pending",),
    # the serving engine's quality surface is the shadow sampler
    "raft_tpu/serving/engine.py": ("ShadowSampler",),
    # the mutable planes: base and delta-tail searches both report
    # certificate/fixup counters (the delta tail is a certified path
    # like any other — ISSUE 11)
    "raft_tpu/mutable/index.py": ("record_pending",),
}

_FLIGHT_MODULE = "raft_tpu/observability/flight.py"

# defining module → (kernel-variant entry points, consuming module):
# the grid-order variants must EXIST where the footprint model and the
# autotuner expect them, and the consumer must actually reference them
# — deleting a variant (or silently unrouting it) would leave tuned
# tables naming a kernel production can't run.
KERNEL_VARIANTS: Dict[str, Tuple[Sequence[str], str]] = {
    "raft_tpu/ops/fused_l2_topk_pallas.py": (
        ("fused_l2_group_topk_packed",
         "fused_l2_group_topk_packed_db",
         "fused_l2_group_topk_packed_dbuf",
         "fused_l2_group_topk_packed_db_q8",
         "fused_l2_group_topk_packed_dbuf_q8"),
        "raft_tpu/distance/knn_fused.py"),
    # the list-major IVF fine-scan family (ISSUE 14): stream each
    # probed list once for all queries probing it; consumed by the
    # ann tier's resolve_fine_scan "list" schedule
    "raft_tpu/ops/fine_scan_pallas.py": (
        ("fine_scan_list_major",
         "fine_scan_list_major_q8"),
        "raft_tpu/ann/ivf_flat.py"),
    # the IVF-PQ ADC kernel (ISSUE 15): the codes slab streamed
    # through the list-major schedule against the VMEM-resident
    # lookup table; consumed by the ann.ivf_pq "pq" schedule
    "raft_tpu/ops/pq_scan_pallas.py": (
        ("pq_scan_list_major",),
        "raft_tpu/ann/ivf_pq.py"),
}

def _decorator_is_instrument(dec: ast.expr) -> bool:
    """True for @instrument, @instrument(...), @observability.instrument,
    and @raft_tpu.observability.instrument(...)."""
    if isinstance(dec, ast.Call):
        dec = dec.func
    if isinstance(dec, ast.Attribute):
        return dec.attr == "instrument"
    return isinstance(dec, ast.Name) and dec.id == "instrument"


def _imports_instrument(tree: ast.Module) -> bool:
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if (node.module or "").startswith("raft_tpu.observability"):
                if any(a.name == "instrument" for a in node.names):
                    return True
        elif isinstance(node, ast.Import):
            if any(a.name.startswith("raft_tpu.observability")
                   for a in node.names):
                return True
    return False


def _calls_attribute(tree: ast.Module, attr: str) -> bool:
    """True when the module contains a call ``<expr>.<attr>(...)``."""
    for node in ast.walk(tree):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr == attr):
            return True
    return False


def check_cost_capture(root: str = _REPO_ROOT,
                       sites: Dict[str, Sequence[str]] = None) -> List[str]:
    """Violations for :data:`COST_CAPTURE_SITES` (empty = clean)."""
    sites = COST_CAPTURE_SITES if sites is None else sites
    errors: List[str] = []
    for rel, methods in sorted(sites.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: cost-capture module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        for m in methods:
            if not _calls_attribute(tree, m):
                errors.append(
                    f"{rel}: no call to profiler .{m}(...) — hot-path "
                    f"measurements would stop flowing through XLA cost "
                    f"capture")
    return errors


def check_kernel_variants(root: str = _REPO_ROOT,
                          variants: Dict[str, Tuple[Sequence[str], str]]
                          = None) -> List[str]:
    """Violations for :data:`KERNEL_VARIANTS` (empty = clean): each
    listed entry point must be defined at module level in its defining
    module AND referenced by name in its consuming module."""
    variants = KERNEL_VARIANTS if variants is None else variants
    errors: List[str] = []
    for rel, (names, consumer_rel) in sorted(variants.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: kernel-variant module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        defined = {n.name for n in tree.body
                   if isinstance(n, (ast.FunctionDef,
                                     ast.AsyncFunctionDef))}
        consumer_path = os.path.join(root, consumer_rel)
        if os.path.exists(consumer_path):
            with open(consumer_path) as f:
                ctree = ast.parse(f.read(), filename=consumer_rel)
            referenced = {n.id for n in ast.walk(ctree)
                          if isinstance(n, ast.Name)}
        else:
            errors.append(f"{consumer_rel}: kernel-variant consumer "
                          f"missing")
            ctree, referenced = None, set()
        for name in names:
            if name not in defined:
                errors.append(f"{rel}: kernel variant {name!r} not "
                              f"defined at module level")
            elif ctree is not None and name not in referenced:
                errors.append(
                    f"{consumer_rel}: kernel variant {name!r} is "
                    f"defined but never referenced — the grid-order "
                    f"routing would silently drop it")
    return errors


def _fault_point_sites(tree: ast.Module) -> set:
    """Literal site names passed to ``fault_point(...)`` calls (plain
    name or attribute spelling)."""
    sites = set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or not node.args:
            continue
        func = node.func
        name = (func.attr if isinstance(func, ast.Attribute)
                else func.id if isinstance(func, ast.Name) else None)
        if name == "fault_point" and isinstance(node.args[0],
                                                ast.Constant):
            sites.add(node.args[0].value)
    return sites


def check_fault_sites(root: str = _REPO_ROOT,
                      sites: Dict[str, Sequence[str]] = None,
                      hot_paths: Dict[str, Sequence[str]] = None
                      ) -> List[str]:
    """Violations for :data:`FAULT_SITES` (empty = clean): every listed
    module carries every listed ``fault_point("<site>")`` call, and
    every HOT_PATHS module is covered by at least one site — a new hot
    path cannot ship uninjectable."""
    sites = FAULT_SITES if sites is None else sites
    hot_paths = HOT_PATHS if hot_paths is None else hot_paths
    errors: List[str] = []
    for rel in sorted(hot_paths):
        if rel not in sites:
            errors.append(
                f"{rel}: hot-path module has no FAULT_SITES entry — "
                f"every hot path must register a fault-injection site")
    for rel, names in sorted(sites.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: fault-site module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        found = _fault_point_sites(tree)
        for site in names:
            if site not in found:
                errors.append(
                    f"{rel}: no fault_point({site!r}) call — the hot "
                    f"path would ship uninjectable (see "
                    f"raft_tpu/resilience/faults.py)")
    return errors


def check_fault_registry(root: str = _REPO_ROOT) -> List[str]:
    """Bidirectional agreement between the sites armed in source and
    ``faults.KNOWN_SITES`` (shared derivation with graftlint's
    registry pass): an armed-but-unregistered site would never get
    matrix coverage; a registered-but-never-armed site is a dead
    registry entry."""
    derived = (_DERIVED if os.path.abspath(root) == _REPO_ROOT
               else load_analysis().registry.derive_registries(root))
    known = derived.known_sites
    if known is None:
        return ["raft_tpu/resilience/faults.py: KNOWN_SITES dict "
                "literal not found — the fault-site registry is gone"]
    errors: List[str] = []
    used = set()
    for rel, sites in sorted(derived.fault_sites.items()):
        for s in sites:
            used.add(s)
            if s not in known:
                errors.append(
                    f"{rel}: fault_point({s!r}) is armed but not "
                    f"registered in faults.KNOWN_SITES — the "
                    f"injection matrix would never test it")
    for s in sorted(set(known) - used):
        errors.append(
            f"raft_tpu/resilience/faults.py: KNOWN_SITES[{s!r}] is "
            f"never armed by any fault_point — dead registry entry")
    return errors


def _known_event_kinds(root: str) -> Optional[set]:
    """The KNOWN_EVENT_KINDS tuple literal parsed out of flight.py (the
    same static-scan pattern as the other gates — no raft_tpu import).
    None when the module/assignment is missing (reported separately)."""
    path = os.path.join(root, _FLIGHT_MODULE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        tree = ast.parse(f.read(), filename=_FLIGHT_MODULE)
    for node in tree.body:
        targets = []
        if isinstance(node, ast.Assign):
            targets = [t.id for t in node.targets
                       if isinstance(t, ast.Name)]
        elif isinstance(node, ast.AnnAssign) and isinstance(node.target,
                                                            ast.Name):
            targets = [node.target.id]
        if "KNOWN_EVENT_KINDS" in targets and node.value is not None:
            try:
                val = ast.literal_eval(node.value)
            except ValueError:
                return None
            return {str(v) for v in val}
    return None


def _referenced_names(tree: ast.Module) -> set:
    """Every plain name and attribute name referenced in the module —
    covers calls, decorators (@instrument(...)), and from-imports."""
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            names.add(node.id)
        elif isinstance(node, ast.Attribute):
            names.add(node.attr)
        elif isinstance(node, ast.ImportFrom):
            names.update(a.name for a in node.names)
    return names


def check_event_sites(root: str = _REPO_ROOT,
                      sites: Dict[str, Sequence[str]] = None,
                      emitters: Dict[str, str] = None,
                      hot_paths: Dict[str, Sequence[str]] = None,
                      fault_sites: Dict[str, Sequence[str]] = None
                      ) -> List[str]:
    """Violations for :data:`EVENT_SITES` (empty = clean): every module
    in HOT_PATHS and every FAULT_SITES module must have an EVENT_SITES
    entry; each listed emitter must be referenced in the module and
    must map (via :data:`EMITTER_KINDS`) to a kind present in
    ``flight.KNOWN_EVENT_KINDS`` — a hot path that emits no timeline
    events cannot be reconstructed from a post-mortem dump."""
    sites = EVENT_SITES if sites is None else sites
    emitters = EMITTER_KINDS if emitters is None else emitters
    hot_paths = HOT_PATHS if hot_paths is None else hot_paths
    fault_sites = FAULT_SITES if fault_sites is None else fault_sites
    errors: List[str] = []
    kinds = _known_event_kinds(root)
    if kinds is None:
        errors.append(f"{_FLIGHT_MODULE}: KNOWN_EVENT_KINDS tuple not "
                      f"found — the flight-recorder vocabulary is gone")
        kinds = set()
    for emitter, kind in sorted(emitters.items()):
        if kinds and kind not in kinds:
            errors.append(
                f"EMITTER_KINDS[{emitter!r}] = {kind!r} is not a "
                f"flight.KNOWN_EVENT_KINDS kind — the gate table and "
                f"the event vocabulary have diverged")
    for rel in sorted(set(hot_paths) | set(fault_sites)):
        if rel not in sites:
            errors.append(
                f"{rel}: hot-path/fault-site module has no EVENT_SITES "
                f"entry — it would be invisible in the flight timeline")
    for rel, names in sorted(sites.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: event-site module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        referenced = _referenced_names(tree)
        for name in names:
            if name not in emitters:
                errors.append(
                    f"{rel}: EVENT_SITES emitter {name!r} is not in "
                    f"EMITTER_KINDS — unknown timeline emitter")
            if name not in referenced:
                errors.append(
                    f"{rel}: no reference to timeline emitter "
                    f"{name!r} — the module would stop emitting "
                    f"flight-recorder events")
    return errors


def check_sharded_merge(root: str = _REPO_ROOT,
                        sites: Dict[str, Sequence[str]] = None,
                        counted: Sequence[str] = None) -> List[str]:
    """Violations for :data:`SHARDED_MERGE_SITES` +
    :data:`COUNTED_COLLECTIVES` (empty = clean)."""
    sites = SHARDED_MERGE_SITES if sites is None else sites
    counted = COUNTED_COLLECTIVES if counted is None else counted
    errors: List[str] = []
    for rel, methods in sorted(sites.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: sharded-merge module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        for m in methods:
            if not _calls_attribute(tree, m):
                errors.append(
                    f"{rel}: no call to comms .{m}(...) — the sharded "
                    f"merge rounds would stop flowing through the "
                    f"collective counters")
    comms_rel = "raft_tpu/comms/comms.py"
    comms_path = os.path.join(root, comms_rel)
    if not os.path.exists(comms_path):
        errors.append(f"{comms_rel}: comms module missing")
        return errors
    with open(comms_path) as f:
        ctree = ast.parse(f.read(), filename=comms_rel)
    counted_labels = {
        node.args[0].value for node in ast.walk(ctree)
        if isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
        and node.func.id == "_count" and node.args
        and isinstance(node.args[0], ast.Constant)}
    for label in counted:
        if label not in counted_labels:
            errors.append(
                f"{comms_rel}: collective {label!r} is not reported "
                f"through _count(...) — its calls/bytes would be "
                f"invisible to the metrics exporters")
    return errors


def check_quality_sites(root: str = _REPO_ROOT,
                        sites: Dict[str, Sequence[str]] = None
                        ) -> List[str]:
    """Violations for :data:`QUALITY_SITES` (empty = clean): every
    certificate/fixup/rescore module must reference its quality
    recorders — the static guarantee that fixup-rate evidence keeps
    flowing into the ``quality`` artifact blocks ``bench_report
    --check`` gates."""
    sites = QUALITY_SITES if sites is None else sites
    errors: List[str] = []
    for rel, names in sorted(sites.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: quality-site module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        referenced = _referenced_names(tree)
        for name in names:
            if name not in referenced:
                errors.append(
                    f"{rel}: no reference to quality recorder "
                    f"{name!r} — certificate/fixup telemetry would "
                    f"silently stop flowing (observability/quality.py)")
    return errors


_SERVING_DIR = "raft_tpu/serving"


def check_serving_coverage(root: str = _REPO_ROOT,
                           sites: Dict[str, Sequence[str]] = None
                           ) -> List[str]:
    """EVERY module under raft_tpu/serving/ (package __init__ excluded)
    must have an EVENT_SITES entry — a serving module invisible in the
    flight timeline cannot be reconstructed from a steady-state trace,
    and the ISSUE-7 gates promise full serving coverage. Structural,
    so a NEW serving module cannot ship unobserved by forgetting the
    table."""
    sites = EVENT_SITES if sites is None else sites
    errors: List[str] = []
    serving_dir = os.path.join(root, _SERVING_DIR)
    if not os.path.isdir(serving_dir):
        return [f"{_SERVING_DIR}/: serving package missing"]
    for name in sorted(os.listdir(serving_dir)):
        if not name.endswith(".py") or name == "__init__.py":
            continue
        rel = f"{_SERVING_DIR}/{name}"
        if rel not in sites:
            errors.append(
                f"{rel}: serving module has no EVENT_SITES entry — "
                f"every raft_tpu/serving/ module must emit timeline "
                f"events")
    return errors


def check(root: str = _REPO_ROOT,
          hot_paths: Dict[str, Sequence[str]] = None) -> List[str]:
    """Returns a list of violation messages (empty = clean)."""
    hot_paths = HOT_PATHS if hot_paths is None else hot_paths
    errors: List[str] = []
    for rel, funcs in sorted(hot_paths.items()):
        path = os.path.join(root, rel)
        if not os.path.exists(path):
            errors.append(f"{rel}: hot-path module missing")
            continue
        with open(path) as f:
            tree = ast.parse(f.read(), filename=rel)
        if not _imports_instrument(tree):
            errors.append(
                f"{rel}: does not import instrument from "
                f"raft_tpu.observability")
        found = {}
        for node in tree.body:  # top-level defs only
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                found[node.name] = node
        for fn in funcs:
            node = found.get(fn)
            if node is None:
                errors.append(f"{rel}: expected hot-path function "
                              f"{fn!r} not found at module level")
            elif not any(_decorator_is_instrument(d)
                         for d in node.decorator_list):
                errors.append(f"{rel}: {fn}() is not decorated with "
                              f"@instrument")
    if hot_paths is HOT_PATHS:
        # the default invocation also gates the cost-capture sites, the
        # kernel-variant presence/consumption assertions, and the
        # sharded-merge collective counting; callers probing a custom
        # hot_paths table (tests) opt out
        errors.extend(check_cost_capture(root))
        errors.extend(check_kernel_variants(root))
        errors.extend(check_sharded_merge(root))
        errors.extend(check_fault_sites(root))
        errors.extend(check_fault_registry(root))
        errors.extend(check_event_sites(root))
        errors.extend(check_serving_coverage(root))
        errors.extend(check_quality_sites(root))
    return errors


def main(argv: Sequence[str] = ()) -> int:
    errors = check()
    for e in errors:
        print(f"check_instrumented: {e}", file=sys.stderr)
    if not errors:
        print(f"check_instrumented: OK — "
              f"{sum(len(v) for v in HOT_PATHS.values())} functions in "
              f"{len(HOT_PATHS)} modules instrumented; "
              f"{sum(len(v) for v in COST_CAPTURE_SITES.values())} "
              f"cost-capture sites verified; "
              f"{sum(len(v[0]) for v in KERNEL_VARIANTS.values())} "
              f"kernel variants present + consumed; "
              f"{sum(len(v) for v in SHARDED_MERGE_SITES.values())} "
              f"sharded-merge sites + "
              f"{len(COUNTED_COLLECTIVES)} counted collectives; "
              f"{sum(len(v) for v in FAULT_SITES.values())} fault-"
              f"injection sites in {len(FAULT_SITES)} modules; "
              f"{len(EVENT_SITES)} timeline-event-emitting modules; "
              f"{len(QUALITY_SITES)} quality-telemetry modules")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
