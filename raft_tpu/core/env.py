"""The single typed accessor for every ``RAFT_TPU_*`` environment knob.

Every knob the tree reads is declared here ONCE with its name, type,
default and one-line doc. graftlint's registry pass pins the chain
``code ⊆ KNOBS ⊆ README env-knob table`` statically: an undeclared
read, an undocumented knob, or a stale README row each fail the lint
gate — the README superset/subset drift this registry replaced can
never come back.

Read knobs through :func:`get` (typed, defaulted) or :func:`raw`
(stripped string or None). Unknown names raise ``KeyError`` — a typo
in a knob name is a bug, not a silent default.

Semantics (matching the historical ad-hoc reads exactly):

- ``bool`` knobs are TRUE iff the variable is set to a non-empty
  string (even ``"0"`` — the historical ``bool(os.environ.get(...))``
  contract, documented rather than changed);
- unset OR empty-after-strip values mean "use the default";
- ``enum`` knobs fall back to their default on an unrecognized value
  (the historical tolerant-parse behavior) — callers that want to
  *reject* instead read :func:`raw` and validate.

Stdlib-only: importable before jax, usable from tools.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, Optional, Tuple

_UNSET = object()


@dataclasses.dataclass(frozen=True)
class Knob:
    name: str
    type: str                  # str | int | float | bool | path | enum
    default: object
    doc: str
    choices: Tuple[str, ...] = ()


KNOBS: Dict[str, Knob] = {}


def _knob(name: str, type: str, default, doc: str,
          choices: Tuple[str, ...] = ()) -> None:
    KNOBS[name] = Knob(name, type, default, doc, choices)


# -- logging / tracing --------------------------------------------------
_knob("RAFT_TPU_LOG_LEVEL", "enum", "info",
      "logger threshold",
      choices=("trace", "debug", "info", "warn", "error"))
_knob("RAFT_TPU_DISABLE_TRACING", "bool", False,
      "turn off nvtx ranges AND all observability spans/metrics/"
      "cost-capture")
_knob("RAFT_TPU_DISABLE_QUALITY", "bool", False,
      "turn off the quality-telemetry plane only")

# -- kernels / routing --------------------------------------------------
_knob("RAFT_TPU_POOL_SELECT", "enum", "xla",
      "fused-KNN pool-selection algorithm",
      choices=("xla", "two_stage", "slotted", "chunked"))
_knob("RAFT_TPU_SELECTK_TABLE", "path", None,
      "override the committed SELECT_K_MATRIX.json AUTO table")
_knob("RAFT_TPU_TUNE_FUSED", "path", None,
      "override the fused-KNN tuning table")
_knob("RAFT_TPU_TUNE_SHARDED", "path", None,
      "override the sharded-KNN tuning table")
_knob("RAFT_TPU_VMEM_BUDGET_MB", "float", None,
      "derate the scoped-VMEM fit budget")
_knob("RAFT_TPU_PALLAS_INTERPRET_DISPATCH", "bool", False,
      "test-only: route non-TPU backends through interpreted Pallas")
_knob("RAFT_TPU_VALIDATE_OUTPUTS", "bool", False,
      "force the finiteness guard on merged KNN outputs")
_knob("RAFT_TPU_DB_DTYPE", "enum", None,
      "fleet default database storage dtype for serving snapshot "
      "builds", choices=("int8", "bf16", "f32"))

# -- sparse plan cache --------------------------------------------------
_knob("RAFT_TPU_TILE_PLAN_CACHE", "path", None,
      "sparse tile-plan persistence directory (0 disables)")
_knob("RAFT_TPU_TILE_PLAN_CACHE_MIN_NNZ", "int", 200000,
      "persistence threshold: smaller conversions skip the disk")
_knob("RAFT_TPU_TILE_PLAN_CACHE_MAX_MB", "float", 2048.0,
      "tile-plan cache LRU size cap (0 = unbounded)")

# -- flight recorder / drift -------------------------------------------
_knob("RAFT_TPU_FLIGHT_EVENTS", "int", 4096,
      "flight-recorder ring capacity in events")
_knob("RAFT_TPU_FLIGHT_DIR", "path", None,
      "automatic post-mortem Perfetto dumps directory")
_knob("RAFT_TPU_FLIGHT_MAX_DUMPS", "int", 16,
      "per-process cap on automatic post-mortem dumps")
_knob("RAFT_TPU_DRIFT_LEDGER", "path", None,
      "persist the model-vs-measured drift ledger to this path")

# -- forensics (blackbox / watchdog) ------------------------------------
_knob("RAFT_TPU_BLACKBOX_PATH", "path", None,
      "crash-durable blackbox ring file mirroring flight events "
      "(unset = forensics off)")
_knob("RAFT_TPU_BLACKBOX_BYTES", "int", 1048576,
      "blackbox ring size in bytes (min 16 KiB)")
_knob("RAFT_TPU_WATCHDOG_S", "float", None,
      "hang-watchdog tick interval in seconds (unset/0 = off)")

# -- resilience ---------------------------------------------------------
_knob("RAFT_TPU_FAULTS", "str", None,
      "fault-injection DSL: site:kind[@call=N][:p=F];…")
_knob("RAFT_TPU_FAULTS_SEED", "int", None,
      "seed for probabilistic fault triggers")
_knob("RAFT_TPU_FAULT_HANG_MAX_S", "float", 30.0,
      "safety cap on injected hang faults with no deadline armed")
_knob("RAFT_TPU_RETRY_MAX", "int", None,
      "global cap on per-site recovery retries (0 = fail fast)")

# -- comms --------------------------------------------------------------
_knob("RAFT_TPU_COORDINATOR", "str", None,
      "multi-process jax.distributed coordinator address")
_knob("RAFT_TPU_P2P_HOST", "str", None,
      "override the host-P2P transport bind address")

# -- serving ------------------------------------------------------------
_knob("RAFT_TPU_SERVING_BUCKETS", "str", None,
      "serving bucket ladder (comma-separated row counts)")
_knob("RAFT_TPU_SERVING_FLUSH_MS", "float", 2.0,
      "serving flush window for partial batches (ms)")
_knob("RAFT_TPU_SERVING_QUEUE_CAP", "int", 4096,
      "serving queue cap in query rows (admission sheds past it)")
_knob("RAFT_TPU_SERVING_DEADLINE_S", "float", None,
      "default per-request deadline budget (unset = none)")
_knob("RAFT_TPU_SERVING_SHADOW_FRAC", "float", 0.0,
      "online recall shadow-sampling fraction of live requests")
_knob("RAFT_TPU_SERVING_SHADOW_FLOOR", "float", 0.95,
      "rolling shadow-recall floor (breach emits a drift event)")
_knob("RAFT_TPU_EXPLAIN_FRAC", "float", 0.0,
      "per-query explain-capture sampling fraction of live searches "
      "(0 = off; constructor explain_frac= wins)")
_knob("RAFT_TPU_DEBUGZ_PORT", "int", None,
      "start the debugz HTTP server on this localhost port at engine "
      "start (0 = ephemeral; unset = no server)")

# -- ANN ----------------------------------------------------------------
_knob("RAFT_TPU_IVF_ROW_QUANTUM", "int", 8,
      "IVF-Flat inverted-list pad quantum")
_knob("RAFT_TPU_ANN_NPROBES", "int", None,
      "fleet default n_probes for search_ivf_flat (read per call)")
_knob("RAFT_TPU_IVF_FINE_SCAN", "enum", "auto",
      "IVF fine-scan schedule: query-major gather, list-major "
      "stream-once kernels, or the cost-model crossover",
      choices=("auto", "query", "list"))
_knob("RAFT_TPU_IVF_PQ_SCAN", "enum", "auto",
      "IVF-PQ schedule: the list-major ADC kernel over the codes "
      "slab, the uncompressed flat fine scan, or the cost-model "
      "crossover (read per call)",
      choices=("auto", "pq", "flat"))
_knob("RAFT_TPU_ANN_PQ_BITS", "int", 8,
      "fleet default code width for build_ivf_pq callers that pass "
      "none (4 or 8 bits per subspace code)")
_knob("RAFT_TPU_ANN_PQ_MODE", "enum", "plain",
      "fleet default build_ivf_pq quantizer mode: plain PQ, an OPQ "
      "learned rotation, or OPQ plus score-aware anisotropic "
      "codeword assignment",
      choices=("plain", "opq", "opq_aniso"))
_knob("RAFT_TPU_ANN_PQ_WIDEN", "int", 4,
      "max widen factor for the PQ certificate middle rung (1 "
      "disables widening; >=2 allows the 512-slot re-ADC pool, >=4 "
      "the 1024-slot pool)")

# -- mutable indexes / durability --------------------------------------
_knob("RAFT_TPU_COMPACT_THRESHOLD", "int", 1024,
      "delta slots that trigger the background compaction fold")
_knob("RAFT_TPU_DELTA_CAP", "int", None,
      "delta slab capacity (default 2x threshold, 8-row quantum)")
_knob("RAFT_TPU_DURABLE_DIR", "path", None,
      "durability-plane directory for ServingEngine(durable=True)")
_knob("RAFT_TPU_WAL_SYNC", "enum", "batch",
      "WAL fsync policy", choices=("always", "batch", "none"))
_knob("RAFT_TPU_WAL_SEGMENT_MB", "float", 64.0,
      "WAL segment rotation size (MB)")

# -- bench harness ------------------------------------------------------
_knob("RAFT_TPU_SOLVERS_BUDGET_S", "float", None,
      "wall-clock budget for benchmarks/bench_solvers_scale.py")


# ------------------------------------------------------------ accessors
def knob(name: str) -> Knob:
    """The declaration for ``name`` (KeyError on unknown — typos in
    knob names must fail loudly, not read an empty default)."""
    return KNOBS[name]


def raw(name: str) -> Optional[str]:
    """The stripped string value, or None when unset/empty. The name
    must be declared."""
    knob(name)
    value = os.environ.get(name)
    if value is None:
        return None
    value = value.strip()
    return value or None


def get(name: str, default=_UNSET):
    """Typed read: the parsed environment value, or the declared
    default (override with ``default=``) when unset, empty, or — for
    ``int``/``float``/``enum`` — unparseable (the historical tolerant
    behavior of every migrated call site)."""
    k = knob(name)
    fallback = k.default if default is _UNSET else default
    if k.type == "bool":
        # set-to-non-empty == True (bool(os.environ.get(...)) contract)
        return os.environ.get(name, "") != ""
    value = raw(name)
    if value is None:
        return fallback
    if k.type in ("str", "path"):
        return value
    if k.type == "enum":
        low = value.lower()
        return low if (not k.choices or low in k.choices) else fallback
    try:
        if k.type == "int":
            return int(value)
        if k.type == "float":
            return float(value)
    except ValueError:
        return fallback
    raise AssertionError(f"unknown knob type {k.type!r}")  # pragma: no cover
