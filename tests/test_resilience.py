"""Resilience runtime tests (ISSUE 5 tentpole).

The fault-injection matrix (every registered site × inject / recover /
exhausted-retries with deterministic triggers), the graceful-degradation
ladders (fused OOM rungs and tournament→allgather→host merge — each rung
bit-identical in ids to the undegraded oracle), deadline scopes
converting injected hangs into ``DeadlineExceededError`` within 2× the
budget, the XLA error error classes, the zero-overhead no-fault contract,
the tune-table degraded-load counter, and the perf-evidence guard that
keeps degraded runs out of the baseline.
"""

import itertools
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from raft_tpu import resilience
from raft_tpu.core import interruptible
from raft_tpu.core.error import (DeadlineExceededError, DeviceError,
                                 LogicError, OutOfMemoryError,
                                 classify_xla_error, device_errors)
from raft_tpu.core.resources import DeviceResources
from raft_tpu.observability import get_registry
from raft_tpu.parallel import make_mesh
from raft_tpu.resilience import (InjectedDeviceError, InjectedFault,
                                 InjectedOutOfMemory, InjectedTimeout,
                                 PoisonedOutputError, RetryPolicy,
                                 deadline, degrade_merge,
                                 fused_degradation_ladder, parse_faults,
                                 run_with_policy)
from raft_tpu.resilience import faults as faults_mod

rng = np.random.default_rng(11)


@pytest.fixture(autouse=True)
def _clean_faults():
    resilience.clear_faults()
    yield
    resilience.clear_faults()
    # never leak a poisoned token into the next test
    interruptible.yield_no_throw()


def _counter_value(name, **labels):
    total = 0.0
    for m in get_registry().collect():
        if m.name == name and all(
                m.labels.get(k) == v for k, v in labels.items()):
            total += m.value
    return total


# ------------------------------------------------------------------
# DSL / classification units
# ------------------------------------------------------------------

def test_parse_faults_dsl():
    specs = parse_faults(
        "aot_compile:oom@call=2; merge_permute:timeout:p=1.0;"
        "plan_cache_read:corrupt")
    assert [(s.site, s.kind, s.nth_call, s.probability)
            for s in specs] == [
        ("aot_compile", "oom", 2, None),
        ("merge_permute", "timeout", None, 1.0),
        ("plan_cache_read", "corrupt", None, None)]


@pytest.mark.parametrize("bad", [
    "siteonly", "s:unknownkind", "s:oom@call=0", "s:oom:p=2.0",
    "s:oom@warp=1", "s:oom:frob=1"])
def test_parse_faults_rejects_malformed(bad):
    with pytest.raises(ValueError):
        parse_faults(bad)


def test_probabilistic_trigger_is_seed_deterministic():
    # same (site, kind, call, seed) → same draw, twice
    s1 = faults_mod.FaultSpec("x", "oom", probability=0.5)
    s2 = faults_mod.FaultSpec("x", "oom", probability=0.5)
    fires1 = [s1.should_fire(9) for _ in range(64)]
    fires2 = [s2.should_fire(9) for _ in range(64)]
    assert fires1 == fires2
    assert any(fires1) and not all(fires1)   # actually probabilistic


def test_classify_xla_error_classes():
    XlaRuntimeError = type("XlaRuntimeError", (Exception,), {})
    assert isinstance(
        classify_xla_error(XlaRuntimeError(
            "RESOURCE_EXHAUSTED: Out of memory allocating 1073741824 "
            "bytes")), OutOfMemoryError)
    assert isinstance(
        classify_xla_error(XlaRuntimeError("INTERNAL: Mosaic failure")),
        DeviceError)
    assert isinstance(
        classify_xla_error(XlaRuntimeError("ABORTED: cross-host sync")),
        DeviceError)
    assert isinstance(
        classify_xla_error(XlaRuntimeError(
            "DEADLINE_EXCEEDED: collective timed out")),
        DeadlineExceededError)
    # scoped-vmem compile OOM classifies as OOM even for generic types
    assert isinstance(
        classify_xla_error(RuntimeError(
            "Mosaic failed: scoped-vmem limit exceeded")),
        OutOfMemoryError)
    # error-class members pass through unchanged
    e = LogicError("x")
    assert classify_xla_error(e) is e
    # unrelated host errors are NOT wrapped
    assert classify_xla_error(ValueError("bad arg")) is None
    assert classify_xla_error(KeyboardInterrupt()) is None


def test_device_errors_scope_wraps_and_chains():
    XlaRuntimeError = type("XlaRuntimeError", (Exception,), {})
    with pytest.raises(OutOfMemoryError) as ei:
        with device_errors("entry"):
            raise XlaRuntimeError("RESOURCE_EXHAUSTED: oom")
    assert isinstance(ei.value.__cause__, XlaRuntimeError)
    assert "entry" in str(ei.value)
    with pytest.raises(ValueError):      # non-device errors untouched
        with device_errors("entry"):
            raise ValueError("host bug")


# ------------------------------------------------------------------
# retry engine
# ------------------------------------------------------------------

def test_run_with_policy_recovers_and_counts():
    calls = []
    before = _counter_value(resilience.RETRIES, site="unit.site")

    def work(attempt):
        calls.append(attempt)
        if len(calls) < 3:
            raise OutOfMemoryError("transient")
        return "ok"

    out = run_with_policy("unit.site", work,
                          policy=RetryPolicy(max_retries=3))
    assert out == "ok" and calls == [0, 1, 2]
    assert _counter_value(resilience.RETRIES, site="unit.site") \
        == before + 2


def test_run_with_policy_exhausts():
    before = _counter_value(resilience.EXHAUSTED, site="unit.exhaust")
    with pytest.raises(OutOfMemoryError):
        run_with_policy("unit.exhaust",
                        lambda a: (_ for _ in ()).throw(
                            OutOfMemoryError("always")),
                        policy=RetryPolicy(max_retries=2))
    assert _counter_value(resilience.EXHAUSTED, site="unit.exhaust") \
        == before + 1


def test_run_with_policy_never_retries_deadline():
    calls = []

    def work(attempt):
        calls.append(attempt)
        raise DeadlineExceededError("budget blown", seconds=1.0)

    with pytest.raises(DeadlineExceededError):
        run_with_policy("unit.deadline", work,
                        policy=RetryPolicy(max_retries=5))
    assert calls == [0]


def test_policy_table_lookup_and_env_cap(monkeypatch):
    table = resilience.PolicyTable()
    assert table.policy_for("runtime.anything").max_retries == 2
    table.set_policy("custom.site", RetryPolicy(max_retries=7))
    assert table.policy_for("custom.site").max_retries == 7
    monkeypatch.setenv("RAFT_TPU_RETRY_MAX", "0")
    assert table.policy_for("custom.site").max_retries == 0
    res = DeviceResources()
    assert res.resilience.policy_for("runtime").max_retries == 0


# ------------------------------------------------------------------
# the fault-injection matrix
# ------------------------------------------------------------------

_aot_names = itertools.count()


def _drive_aot():
    from raft_tpu.runtime.entry_points import _aot_call

    res = DeviceResources()
    return _aot_call(res, f"resil_entry_{next(_aot_names)}", (),
                     lambda a: a + 1.0, jnp.ones(3))


def _mesh(p):
    return make_mesh({"x": p}, devices=jax.devices()[:p])


def _coo_small():
    from raft_tpu.core.sparse_types import COOMatrix

    r = rng.integers(0, 64, 200).astype(np.int32)
    c = rng.integers(0, 64, 200).astype(np.int32)
    v = rng.normal(size=200).astype(np.float32)
    return COOMatrix(r, c, v, (64, 64))


def _drive_kmeans():
    """Routes through BOTH kmeans sites: kmeans_fit fires at entry,
    kmeans_iteration inside the first Lloyd pass."""
    from raft_tpu.cluster import kmeans_fit

    X = rng.normal(size=(32, 8)).astype(np.float32)
    return kmeans_fit(None, X, 2, max_iter=1, seed=0)


_ivf_index = None


def _ivf_small():
    global _ivf_index
    if _ivf_index is None:
        from raft_tpu.ann import build_ivf_flat

        _ivf_index = build_ivf_flat(
            None, rng.normal(size=(64, 8)).astype(np.float32),
            n_lists=4, max_iter=2, seed=0)
    return _ivf_index


def _drive_ivf_build():
    from raft_tpu.ann import build_ivf_flat

    return build_ivf_flat(
        None, rng.normal(size=(64, 8)).astype(np.float32),
        n_lists=4, max_iter=1, seed=0)


def _drive_ivf_search():
    """The search fault site fires at entry, before the coarse probe —
    the prebuilt tiny index keeps the driver cheap."""
    from raft_tpu.ann import search_ivf_flat

    return search_ivf_flat(None, _ivf_small(),
                           np.ones((2, 8), np.float32), 2, n_probes=2)


def _drive_pq_train():
    """The pq_train site fires before the per-subspace codebook loop —
    a failing codebook train must surface at build, never ship a
    silently-flat index (4-bit keeps the 2^pq_bits codeword demand
    inside the 64-row driver)."""
    from raft_tpu.ann import build_ivf_pq

    return build_ivf_pq(
        None, rng.normal(size=(64, 8)).astype(np.float32),
        n_lists=4, pq_bits=4, max_iter=1, pq_max_iter=1, seed=0)


def _drive_opq_train():
    """The opq_train site fires before the OPQ alternating
    minimization — a failing rotation train must surface at build,
    never ship a silently-unrotated index."""
    from raft_tpu.ann import build_ivf_pq

    return build_ivf_pq(
        None, rng.normal(size=(64, 8)).astype(np.float32),
        n_lists=4, pq_bits=4, max_iter=1, pq_max_iter=1, seed=0,
        pq_mode="opq", opq_iters=1)


_mutable_index = None


def _mutable_small():
    """A tiny shared MutableIndex (auto-compaction off — the matrix
    drivers route through one site each; the high watermark keeps the
    upsert/delete drivers from triggering a background fold)."""
    global _mutable_index
    if _mutable_index is None:
        from raft_tpu.mutable import MutableIndex

        _mutable_index = MutableIndex(
            rng.normal(size=(64, 8)).astype(np.float32),
            T=256, Qb=32, g=2, compact_threshold=10_000,
            auto_compact=False)
    return _mutable_index


def _drive_mutate_ingest():
    from raft_tpu.mutable import apply_upsert

    return apply_upsert(_mutable_small(), [100],
                        rng.normal(size=(1, 8)).astype(np.float32))


def _drive_tombstone_apply():
    from raft_tpu.mutable import apply_delete

    return apply_delete(_mutable_small(), [0])


def _drive_compact_fold():
    """The fault site fires at the top of the fold, BEFORE the rebuild
    — the old snapshot provably keeps serving (the dedicated torn-
    generation test below pins the evidence)."""
    return _mutable_small().compact(block=True)


def _drive_wal(sync: str):
    """Cheap route through the WAL sites: one append on a throwaway
    writer (sync='always' routes the fsync seam on the same call)."""
    import tempfile

    from raft_tpu.mutable.wal import OP_DELETE, WalWriter, encode_delete

    w = WalWriter(tempfile.mkdtemp(), sync=sync)
    try:
        return w.append(OP_DELETE, encode_delete(np.array([1])))
    finally:
        w.close()


def _drive_checkpoint_write():
    """Cheap route through the checkpoint sites: one tiny store write
    (checkpoint_write fires before any byte lands, manifest_commit at
    the two-phase pointer seam of the same call)."""
    import tempfile

    from raft_tpu.mutable.checkpoint import CheckpointStore

    store = CheckpointStore(tempfile.mkdtemp())
    return store.write(np.ones((4, 4), np.float32),
                       np.arange(4, dtype=np.int32), lsn=1,
                       generation=0)


_serving_engine = None


def _drive_serving_enqueue():
    """Cheap route through the serving_enqueue fault site: the fault
    fires at admission, before the engine needs a batcher thread."""
    global _serving_engine
    from raft_tpu.serving import ServingEngine

    if _serving_engine is None:
        from raft_tpu.distance.knn_fused import prepare_knn_index

        idx = prepare_knn_index(
            rng.normal(size=(64, 8)).astype(np.float32),
            passes=3, T=256, Qb=32, g=2)
        _serving_engine = ServingEngine(idx, k=2, buckets=(8,))
    return _serving_engine.submit(np.ones((2, 8), np.float32))


def _always_raise_drivers():
    """site → cheap call routing through that site (the fault fires at
    the site before real work starts, so dummy-sized args are fine)."""
    from raft_tpu.comms.host_comms import HostComms
    from raft_tpu.distance.fused_l2nn import fused_l2_nn_argmin
    from raft_tpu.distance.knn_fused import knn_fused
    from raft_tpu.distance.pairwise import pairwise_distance
    from raft_tpu.matrix.select_k import select_k
    from raft_tpu.matrix.select_k_chunked import select_k_chunked
    from raft_tpu.matrix.select_k_slotted import select_k_slotted
    from raft_tpu.solver.linear_assignment import solve_lap
    from raft_tpu.sparse.sharded import spmv_sharded
    from raft_tpu.sparse.tiled import tile_csr
    from raft_tpu.tune.fused import autotune_fused
    from raft_tpu.tune.sharded import autotune_sharded

    x = np.ones((2, 8), np.float32)
    hc = HostComms(_mesh(2), "x")
    return {
        "select_k": lambda: select_k(
            None, np.array([[3.0, 1.0, 2.0]]), k=2),
        "select_k_chunked": lambda: select_k_chunked(
            np.ones((2, 64), np.float32), None, 4, True),
        "select_k_slotted": lambda: select_k_slotted(
            np.ones((2, 64), np.float32), None, 4, True),
        "pairwise_distance": lambda: pairwise_distance(None, x),
        "fused_l2nn": lambda: fused_l2_nn_argmin(None, x, x),
        "knn_fused": lambda: knn_fused(
            x, np.ones((16, 8), np.float32), k=2),
        "tile_csr": lambda: tile_csr(_coo_small(), impl="numpy"),
        "spmv_sharded": lambda: spmv_sharded(
            None, np.ones(4, np.float32)),
        "solve_lap": lambda: solve_lap(
            None, np.eye(4, dtype=np.float32)),
        "autotune_fused": lambda: autotune_fused(
            shape=(8, 64, 8, 2), out_path=None, measure=False),
        "autotune_sharded": lambda: autotune_sharded(
            shape=(8, 64, 8, 2), p=2, out_path=None, measure=False),
        "host_collective": lambda: hc.allreduce(
            np.ones((2, 2), np.float32)),
        "host_barrier": hc.barrier,
        "host_sync": lambda: hc.sync_stream(jnp.ones(2)),
        "aot_compile": _drive_aot,
        "aot_dispatch": _drive_aot,
        # clustering + ANN tier: the fit entry fires kmeans_fit, the
        # Lloyd loop fires kmeans_iteration on the same drive; the IVF
        # pair drives build (which the search driver re-runs cheaply —
        # only the ARMED site fires)
        "kmeans_fit": _drive_kmeans,
        "kmeans_iteration": _drive_kmeans,
        # int8 index quantization: the site fires in prepare_knn_index
        # before the quantize prep runs (db-major geometry keeps the
        # tiny driver inside the packed envelope)
        "quantize_index": lambda: __import__(
            "raft_tpu.distance.knn_fused",
            fromlist=["prepare_knn_index"]).prepare_knn_index(
                np.ones((64, 8), np.float32), passes=1, T=256, Qb=32,
                g=2, grid_order="db", db_dtype="int8"),
        "ivf_build": _drive_ivf_build,
        "ivf_search": _drive_ivf_search,
        # IVF-PQ compressed tier: the codebook-train and OPQ
        # rotation-train seams raise at build; the ADC dispatch seam
        # (pq_scan) DEGRADES to the flat scan and the widen-rung
        # re-ADC seam (pq_widen) DEGRADES to the exact rerun instead
        # of raising — dedicated id-parity tests in
        # tests/test_ivf_pq.py / tests/test_pq_quality.py
        "pq_train": _drive_pq_train,
        "opq_train": _drive_opq_train,
        "pq_scan": None,
        "pq_widen": None,
        # fine-scan schedule autotuner: deterministic model sweep
        "autotune_fine_scan": lambda: __import__(
            "raft_tpu.tune.ivf",
            fromlist=["autotune_fine_scan"]).autotune_fine_scan(
                shape=(8, 64, 8, 2), lists=(4,)),
        "serving_enqueue": _drive_serving_enqueue,
        # mutable indexes: ingest / tombstone / compaction fold — each
        # site fires before any state change, so the shared index stays
        # consistent across the matrix
        "mutate_ingest": _drive_mutate_ingest,
        "tombstone_apply": _drive_tombstone_apply,
        "compact_fold": _drive_compact_fold,
        # durability plane (ISSUE 12): WAL append/fsync + checkpoint
        # write/commit — the same four seams the SIGKILL crash matrix
        # (tests/test_durability.py) takes to process death
        "wal_append": lambda: _drive_wal("batch"),
        "wal_fsync": lambda: _drive_wal("always"),
        "checkpoint_write": _drive_checkpoint_write,
        "manifest_commit": _drive_checkpoint_write,
        "sharded_dispatch": None,      # dedicated ladder tests below
        "merge_permute": None,
        "merge_allgather": None,
        # list-major fine scan DEGRADES to query-major instead of
        # raising — dedicated id-parity test in tests/test_fine_scan.py
        "fine_scan_list": None,
        "tune_table_read": None,       # corrupt-kind tests below
        "plan_cache_read": None,
        # serving flush/snapshot: dedicated batch/swap injection tests
        # in tests/test_serving.py (the engine needs a running batcher)
        "serving_flush": None,
        "serving_snapshot": None,
    }


def test_every_known_site_has_matrix_coverage():
    """A site registered in faults.KNOWN_SITES but absent from the
    matrix driver table would ship untested — and the static FAULT_SITES
    gate must agree with the runtime registry."""
    drivers = _always_raise_drivers()
    assert set(drivers) == set(resilience.KNOWN_SITES)
    import tools.check_instrumented as ci

    static_sites = {s for names in ci.FAULT_SITES.values()
                    for s in names}
    assert static_sites <= set(resilience.KNOWN_SITES)
    assert set(ci.HOT_PATHS) <= set(ci.FAULT_SITES)


@pytest.mark.parametrize("site", sorted(
    s for s, drv in _always_raise_drivers().items() if drv is not None))
def test_inject_always_raises(site):
    """Inject leg of the matrix: an always-armed ``error`` fault at any
    plain site surfaces as the classified injected exception (retry
    sites exhaust their bounded retries first — still the injected
    type), and the injection counter advances."""
    drivers = _always_raise_drivers()
    before = _counter_value(resilience.INJECTIONS, site=site)
    resilience.configure_faults(f"{site}:error")
    with pytest.raises(InjectedDeviceError):
        drivers[site]()
    assert _counter_value(resilience.INJECTIONS, site=site) > before


def test_inject_nth_call_recovers_aot():
    """Recover leg: a compile OOM on call 1 only — the retry recompiles
    and the entry succeeds, with the retry counted."""
    resilience.configure_faults("aot_compile:oom@call=1")
    before = _counter_value(resilience.RETRIES)
    out = _drive_aot()
    np.testing.assert_allclose(np.asarray(out), 2.0)
    assert _counter_value(resilience.RETRIES) > before


def test_inject_always_exhausts_aot():
    """Exhausted leg: an always-firing dispatch OOM burns every retry
    and re-raises the injected OOM, counting the exhaustion."""
    resilience.configure_faults("aot_dispatch:oom")
    before = _counter_value(resilience.EXHAUSTED)
    with pytest.raises(InjectedOutOfMemory):
        _drive_aot()
    assert _counter_value(resilience.EXHAUSTED) > before


def test_injected_faults_carry_marker():
    for exc in (InjectedOutOfMemory("x"), InjectedDeviceError("x"),
                InjectedTimeout("x")):
        assert isinstance(exc, InjectedFault)
        assert isinstance(exc, DeviceError)


# ------------------------------------------------------------------
# mutable indexes: a mid-compaction crash keeps the old snapshot
# ------------------------------------------------------------------

def _fresh_mutable(m=128, d=8, threshold=32):
    from raft_tpu.mutable import MutableIndex

    y = rng.normal(size=(m, d)).astype(np.float32)
    return y, MutableIndex(y, T=256, Qb=32, g=2,
                           compact_threshold=threshold,
                           auto_compact=False)


def test_compact_fold_crash_keeps_old_snapshot():
    """An injected crash at the ``compact_fold`` site (and at the
    ``serving_snapshot`` rebuild inside the fold) must leave the old
    generation serving with the delta/tombstone state intact — no torn
    generation, and a later clean compaction succeeds."""
    from raft_tpu.distance.knn_fused import knn_fused
    from raft_tpu.mutable import apply_delete, apply_upsert, search_view

    y, mi = _fresh_mutable()
    d = y.shape[1]
    apply_delete(mi, [0, 1])
    apply_upsert(mi, np.arange(500, 540),
                 rng.normal(size=(40, d)).astype(np.float32))
    gen0 = mi.generation
    seq0 = mi.seq
    stats0 = mi.stats()

    # leg 1: crash at the fold entry (before any rebuild)
    resilience.configure_faults("compact_fold:error")
    with pytest.raises(InjectedDeviceError):
        mi.compact(block=True)
    assert mi.generation == gen0
    assert not mi.folding
    st = mi.stats()
    assert st["delta_rows"] == stats0["delta_rows"]
    assert st["tombstones"] == stats0["tombstones"]

    # leg 2: crash inside the snapshot rebuild (SnapshotStore swallows,
    # the fold reports failure) — old snapshot still serving
    resilience.configure_faults("serving_snapshot:error")
    with pytest.raises(Exception):
        mi.compact(block=True)
    assert not mi.folding
    assert mi.stats()["delta_rows"] == stats0["delta_rows"]

    # the surviving state still answers exactly like the rebuild oracle
    resilience.clear_faults()
    x = rng.normal(size=(5, d)).astype(np.float32)
    live = np.ones(y.shape[0], bool)
    live[[0, 1]] = False
    rows = np.concatenate(
        [y[live], np.asarray(mi._d_rows[:40], np.float32)])
    exts = np.concatenate([np.arange(y.shape[0])[live],
                           np.arange(500, 540)])
    ov, oi = knn_fused(x, rows, 5, passes=3, T=256, Qb=32, g=2)
    sv, si = search_view(mi, x, 5)
    assert np.array_equal(np.asarray(sv), np.asarray(ov))
    assert np.array_equal(np.sort(np.asarray(si), 1),
                          np.sort(exts[np.asarray(oi)], 1))

    # a clean compaction now lands: generation advances, delta folds
    assert mi.compact(block=True)
    assert mi.generation > gen0
    assert mi.seq > seq0
    st = mi.stats()
    assert st["delta_rows"] == 0 and st["tombstones"] == 0
    sv, si = search_view(mi, x, 5)
    assert np.array_equal(np.asarray(sv), np.asarray(ov))
    assert np.array_equal(np.sort(np.asarray(si), 1),
                          np.sort(exts[np.asarray(oi)], 1))


# ------------------------------------------------------------------
# sharded ladder: oracle parity at every rung + injected recovery
# ------------------------------------------------------------------

M, D, K, NQ = 4100, 32, 7, 33
CFG = dict(T=256, Qb=32, g=2)


@pytest.fixture(scope="module")
def sharded_data():
    from raft_tpu.distance.knn_fused import knn_fused

    y = rng.normal(size=(M, D)).astype(np.float32)
    x = rng.normal(size=(NQ, D)).astype(np.float32)
    ov, oi = knn_fused(x, y, k=K, passes=3, **CFG)
    return x, y, np.asarray(ov), np.asarray(oi)


def _assert_oracle(si, sv, oi, ov):
    assert np.array_equal(np.asarray(sv), ov)
    assert np.array_equal(np.sort(np.asarray(si), 1), np.sort(oi, 1))


@pytest.mark.parametrize("merge", ["tournament", "allgather", "host"])
def test_merge_ladder_rungs_match_oracle(sharded_data, merge):
    """Every rung of the merge ladder — including the host-side bottom
    rung — is bit-identical in values and id sets to the single-device
    oracle."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    x, y, ov, oi = sharded_data
    sv, si = knn_fused_sharded(x, y, K, mesh=_mesh(4), merge=merge,
                               passes=3, **CFG)
    _assert_oracle(si, sv, oi, ov)


def test_collective_failure_walks_merge_ladder(sharded_data):
    """Injected collective timeout at the tournament rung degrades to
    allgather; with both collective rungs failing it lands on the host
    merge — correct bits either way, every step counted."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    x, y, ov, oi = sharded_data
    site = "distance.knn_fused_sharded"
    before = _counter_value(resilience.DEGRADATIONS, site=site)
    resilience.configure_faults("merge_permute:timeout")
    sv, si = knn_fused_sharded(x, y, K, mesh=_mesh(4),
                               merge="tournament", passes=3, **CFG)
    _assert_oracle(si, sv, oi, ov)
    resilience.configure_faults(
        "merge_permute:timeout;merge_allgather:timeout")
    sv, si = knn_fused_sharded(x, y, K, mesh=_mesh(4),
                               merge="tournament", passes=3, **CFG)
    _assert_oracle(si, sv, oi, ov)
    assert _counter_value(resilience.DEGRADATIONS, site=site) \
        >= before + 3    # t->a, then t->a + a->h


def test_oom_ladder_fit_rungs_match_oracle(sharded_data):
    """Injected dispatch OOM walks the fit ladder (Qb halves) and the
    recovered result matches the oracle bit-for-bit."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    x, y, ov, oi = sharded_data
    resilience.configure_faults("sharded_dispatch:oom@call=1")
    sv, si = knn_fused_sharded(x, y, K, mesh=_mesh(4),
                               merge="allgather", passes=3, **CFG)
    _assert_oracle(si, sv, oi, ov)


def test_nan_poisoning_detected_and_retried(sharded_data):
    """NaN-poisoned output is caught by the (fault-armed) finiteness
    guard and retried clean; an always-poisoning fault exhausts retries
    and surfaces as PoisonedOutputError."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    x, y, ov, oi = sharded_data
    resilience.configure_faults("sharded_dispatch:nan@call=1")
    sv, si = knn_fused_sharded(x, y, K, mesh=_mesh(4),
                               merge="allgather", passes=3, **CFG)
    _assert_oracle(si, sv, oi, ov)
    resilience.configure_faults("sharded_dispatch:nan")
    with pytest.raises(PoisonedOutputError):
        knn_fused_sharded(x, y, K, mesh=_mesh(4), merge="allgather",
                          passes=3, **CFG)


def test_fused_degradation_ladder_rungs_valid_and_oracle(sharded_data):
    """The config-level OOM ladder: every generated rung passes the
    production fit predicate, terminates, and (for a sample of rungs)
    reproduces the oracle ids through the sharded pipeline."""
    from raft_tpu.distance.knn_fused import _valid_cfg, fit_config
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    rungs = list(fused_degradation_ladder(
        T=CFG["T"], Qb=CFG["Qb"], g=CFG["g"], grid_order="db", d=D,
        passes=3, micro_batches=1, max_micro_batches=8))
    assert rungs, "ladder must yield at least one rung"
    actions = [r.action.split(":")[1] for r in rungs]
    # the documented rung order: Qb first, then T, g, grid_order, nb
    order = {"Qb": 0, "T": 1, "g": 2, "grid_order": 3,
             "micro_batches": 4}
    assert [order[a] for a in actions] == sorted(
        order[a] for a in actions)
    assert any(a == "grid_order" for a in actions)  # packed→unpacked rung
    for r in rungs:
        assert _valid_cfg(r.T, r.Qb, r.g, r.grid_order)
        assert fit_config(r.T, r.Qb, D, 3, r.g, r.grid_order) \
            == (r.T, r.Qb)
    x, y, ov, oi = sharded_data
    for r in [rungs[0], rungs[-2]]:
        sv, si = knn_fused_sharded(
            x, y, K, mesh=_mesh(4), merge="allgather", passes=3,
            T=r.T, Qb=r.Qb, g=r.g, grid_order=r.grid_order,
            micro_batches=r.micro_batches)
        # a rung that re-tiles (T/g) perturbs the packed low bits —
        # the acceptance bound: ids identical, values within the
        # pack-perturbation envelope
        assert np.array_equal(np.sort(np.asarray(si), 1),
                              np.sort(oi, 1))
        np.testing.assert_allclose(np.sort(np.asarray(sv), 1),
                                   np.sort(ov, 1), atol=1e-3)


def test_vmem_budget_derate_knob(monkeypatch):
    """RAFT_TPU_VMEM_BUDGET_MB derates every fit predicate in one
    place: a config that fits the built-in budget shrinks under a
    tighter one (the operator's last-resort answer to real Mosaic
    rejects the model passes)."""
    from raft_tpu.distance.knn_fused import fit_config
    from raft_tpu.ops.fused_l2_topk_pallas import (VMEM_BUDGET,
                                                   vmem_budget)

    assert vmem_budget() == VMEM_BUDGET
    monkeypatch.setenv("RAFT_TPU_VMEM_BUDGET_MB", "junk")
    assert vmem_budget() == VMEM_BUDGET
    monkeypatch.setenv("RAFT_TPU_VMEM_BUDGET_MB", "2")
    assert vmem_budget() == 2 << 20
    assert fit_config(2048, 256, 128, 3) != (2048, 256)
    monkeypatch.delenv("RAFT_TPU_VMEM_BUDGET_MB")
    assert fit_config(2048, 256, 128, 3) == (2048, 256)


def test_degrade_merge_ladder_terminates():
    assert degrade_merge("tournament") == "allgather"
    assert degrade_merge("allgather") == "host"
    assert degrade_merge("host") is None
    assert degrade_merge("garbage") is None


# ------------------------------------------------------------------
# deadlines & watchdog
# ------------------------------------------------------------------

def test_deadline_converts_poll_loop():
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError) as ei:
        with deadline(0.2, label="poll"):
            while True:
                interruptible.yield_()
                time.sleep(0.002)
    assert time.monotonic() - t0 < 0.4          # within 2× the budget
    assert ei.value.seconds == 0.2


def test_deadline_carries_span_stack():
    from raft_tpu.core import nvtx

    with pytest.raises(DeadlineExceededError) as ei:
        with nvtx.annotate("outer_op"):
            with deadline(0.1, label="spans"):
                while True:
                    interruptible.yield_()
                    time.sleep(0.002)
    assert "outer_op" in ei.value.span_stack


def test_deadline_converts_injected_collective_hang(sharded_data):
    """The acceptance criterion: an injected hang at the merge
    collective + a deadline scope = DeadlineExceededError within 2× the
    configured deadline (not a hang, not a retry loop)."""
    from raft_tpu.distance.knn_sharded import knn_fused_sharded

    x, y, _, _ = sharded_data
    resilience.configure_faults("merge_allgather:hang")
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        with deadline(0.5, label="merge-hang"):
            knn_fused_sharded(x, y, K, mesh=_mesh(4),
                              merge="allgather", passes=3, **CFG)
    assert time.monotonic() - t0 < 1.0


def test_deadline_scope_exits_clean():
    with deadline(5.0):
        pass
    # a fast body leaves no pending cancellation behind
    interruptible.yield_()
    # an expired deadline raises at scope exit even with no poll inside
    with pytest.raises(DeadlineExceededError):
        with deadline(0.05):
            time.sleep(0.15)
    interruptible.yield_()          # and the token is clean afterwards


def test_deadline_scopes_thread_isolated():
    """ISSUE 7 satellite regression: two CONCURRENT deadline scopes on
    different threads — the short one fires on its own thread only; the
    long one's work is never cancelled by it (tokens are thread-local,
    arms are lock-guarded)."""
    import threading

    outcomes = {}
    barrier = threading.Barrier(2)

    def short_lived():
        barrier.wait()
        try:
            with deadline(0.15, label="short"):
                while True:
                    interruptible.yield_()
                    time.sleep(0.002)
        except DeadlineExceededError as e:
            outcomes["short"] = e

    def long_lived():
        barrier.wait()
        try:
            with deadline(30.0, label="long"):
                t0 = time.monotonic()
                # polls well past the short scope's expiry
                while time.monotonic() - t0 < 0.4:
                    interruptible.yield_()
                    time.sleep(0.002)
            outcomes["long"] = "ok"
        except DeadlineExceededError as e:     # pragma: no cover
            outcomes["long"] = e

    ts = [threading.Thread(target=short_lived),
          threading.Thread(target=long_lived)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=10)
    assert isinstance(outcomes.get("short"), DeadlineExceededError)
    assert outcomes.get("long") == "ok"


def test_deadline_scopes_reentrant_nested():
    """Nested scopes on ONE thread: the inner (first-to-expire) scope
    raises with ITS label; the outer scope stays armed and exits clean
    — and the token is unpoisoned afterwards."""
    with deadline(30.0, label="outer"):
        with pytest.raises(DeadlineExceededError) as ei:
            with deadline(0.1, label="inner"):
                while True:
                    interruptible.yield_()
                    time.sleep(0.002)
        assert "inner" in str(ei.value)
        # the outer scope's watchdog has not fired — the thread's next
        # cancellation point must NOT raise
        interruptible.yield_()
    interruptible.yield_()          # token clean after both scopes


def test_deadline_both_scopes_expired_report_earliest():
    """Both nested scopes expire before any cancellation point: the
    earliest expiry (the inner scope's) is reported, each scope clears
    only its own record, and nothing leaks onto the token."""
    with pytest.raises(DeadlineExceededError) as ei:
        with deadline(0.05, label="outer-short"):
            with deadline(0.1, label="inner-late"):
                time.sleep(0.25)        # no polls: both timers fire
                interruptible.yield_()
    assert "outer-short" in str(ei.value)
    interruptible.yield_()              # token clean afterwards


def test_interruptible_token_is_thread_local_not_ident_keyed():
    """A recycled thread ident must never inherit a dead thread's
    poisoned token: each new thread's first get_token() yields a fresh,
    uncancelled token even when the registry holds a stale entry for
    the same ident."""
    import threading

    idents = []

    def poison():
        idents.append(threading.get_ident())
        interruptible.cancel()          # own token, left poisoned

    t = threading.Thread(target=poison)
    t.start()
    t.join()
    # the dead thread's registry entry is still poisoned...
    stale = interruptible.get_token(idents[0])
    assert stale.cancelled
    # ...but any NEW thread's own token is created clean (thread-local
    # lookup, never the ident registry), even if its ident collides
    out = {}

    def check():
        tok = interruptible.get_token()
        out["cancelled"] = tok.cancelled

    t3 = threading.Thread(target=check)
    t3.start()
    t3.join()
    assert out["cancelled"] is False


def test_hostcomms_sync_stream_nothrow_abort_status():
    from raft_tpu.comms.comms import Status
    from raft_tpu.comms.host_comms import HostComms

    hc = HostComms(_mesh(2), "x")
    resilience.configure_faults("host_sync:hang")
    with deadline(0.2, label="sync"):
        status = hc.sync_stream(jnp.ones(2), nothrow=True)
    assert status is Status.ABORT
    resilience.configure_faults("host_sync:error")
    assert hc.sync_stream(jnp.ones(2), nothrow=True) is Status.ERROR
    resilience.clear_faults()
    assert hc.sync_stream(jnp.ones(2)) is Status.SUCCESS


def test_hostcomms_barrier_hang_converts():
    from raft_tpu.comms.host_comms import HostComms

    hc = HostComms(_mesh(2), "x")
    resilience.configure_faults("host_barrier:hang")
    t0 = time.monotonic()
    with pytest.raises(DeadlineExceededError):
        with deadline(0.2, label="barrier"):
            hc.barrier()
    assert time.monotonic() - t0 < 0.4


# ------------------------------------------------------------------
# zero-overhead no-fault contract
# ------------------------------------------------------------------

def test_no_fault_parity_sharded(sharded_data):
    """With no faults armed the resilience layer must not change one
    bit of the result NOR add compiled programs (the jit cache grows
    only by the single expected program)."""
    from raft_tpu.distance import knn_sharded as ks

    x, y, ov, oi = sharded_data
    assert not resilience.faults_active()
    assert resilience.fault_point("sharded_dispatch") is None
    sv, si = ks.knn_fused_sharded(x, y, K, mesh=_mesh(4),
                                  merge="allgather", passes=3, **CFG)
    n_progs = len(ks._SHARDED_FUSED_CACHE)
    sv2, si2 = ks.knn_fused_sharded(x, y, K, mesh=_mesh(4),
                                    merge="allgather", passes=3, **CFG)
    assert len(ks._SHARDED_FUSED_CACHE) == n_progs
    _assert_oracle(si, sv, oi, ov)
    assert np.array_equal(np.asarray(sv), np.asarray(sv2))
    assert np.array_equal(np.asarray(si), np.asarray(si2))


def test_no_fault_parity_aot_cache_hits():
    from raft_tpu.runtime.entry_points import _aot_call

    res = DeviceResources()
    args = (jnp.ones(4),)
    _aot_call(res, "parity_entry", (), lambda a: a * 3.0, *args)
    assert (res.compile_cache.hits, res.compile_cache.misses) == (0, 1)
    out = _aot_call(res, "parity_entry", (), lambda a: a * 3.0, *args)
    assert (res.compile_cache.hits, res.compile_cache.misses) == (1, 1)
    np.testing.assert_allclose(np.asarray(out), 3.0)


# ------------------------------------------------------------------
# corrupt persistent reads (tune tables / plan cache)
# ------------------------------------------------------------------

@pytest.fixture()
def _fresh_tables(monkeypatch):
    """Reset the lazy tune-table singletons around a test."""
    import raft_tpu.distance.knn_fused as kf
    import raft_tpu.tune.sharded as ts
    from raft_tpu.tune.fused import _reset_degraded_warnings

    old_f, old_s = kf._TUNED, ts._TUNED_SHARDED
    kf._TUNED, ts._TUNED_SHARDED = ..., ...
    _reset_degraded_warnings()
    yield monkeypatch
    kf._TUNED, ts._TUNED_SHARDED = old_f, old_s


def _degraded(table, reason):
    from raft_tpu.tune.fused import TABLE_DEGRADED

    return _counter_value(TABLE_DEGRADED, table=table, reason=reason)


def test_tune_table_degraded_reasons(tmp_path, _fresh_tables):
    """Every degrade path of both loaders is counted with its reason
    label and the loader falls back to built-ins instead of raising."""
    import raft_tpu.distance.knn_fused as kf
    import raft_tpu.tune.sharded as ts
    monkeypatch = _fresh_tables

    def reload_fused():
        kf._TUNED = ...
        return kf.fused_config(3)

    # unreadable: garbage bytes
    p = tmp_path / "garbage.json"
    p.write_text("{not json")
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(p))
    before = _degraded("fused", "unreadable")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "unreadable") == before + 1
    # missing (explicitly-named path only)
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED",
                       str(tmp_path / "absent.json"))
    before = _degraded("fused", "missing")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "missing") == before + 1
    # invalid: structurally corrupt
    p = tmp_path / "invalid.json"
    p.write_text('{"rows": "not-a-list"}')
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(p))
    before = _degraded("fused", "invalid")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "invalid") == before + 1
    # future schema
    p = tmp_path / "future.json"
    p.write_text('{"schema": 99, "rows": []}')
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(p))
    before = _degraded("fused", "future_schema")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "future_schema") == before + 1
    # row rejected by the scoped-VMEM fit at the table's d
    p = tmp_path / "hot_row.json"
    p.write_text('{"schema": 3, "shape": [2048, 1000000, 4096, 64], '
                 '"rows": [{"T": 4096, "Qb": 1024, "g": 32, '
                 '"passes": 3, "seconds": 0.1}]}')
    monkeypatch.setenv("RAFT_TPU_TUNE_FUSED", str(p))
    before = _degraded("fused", "row_rejected")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "row_rejected") == before + 1
    # injected corrupt read (the tune_table_read fault site)
    resilience.configure_faults("tune_table_read:corrupt")
    before = _degraded("fused", "unreadable")
    assert reload_fused() == kf._BUILTIN_CONFIG
    assert _degraded("fused", "unreadable") == before + 1
    resilience.clear_faults()
    # sharded: shard-count mismatch counts per degraded load
    good = {"schema": 3, "n_shards": 4, "rows": [],
            "best": {"T": 512, "Qb": 256, "g": 2, "merge": "allgather",
                     "micro_batches": 2, "passes": 3}}
    p = tmp_path / "sharded.json"
    import json as _json

    p.write_text(_json.dumps(good))
    monkeypatch.setenv("RAFT_TPU_TUNE_SHARDED", str(p))
    ts._TUNED_SHARDED = ...
    assert ts.sharded_config(4)["micro_batches"] == 2
    before = _degraded("sharded", "shard_mismatch")
    assert ts.sharded_config(8) == {}
    assert _degraded("sharded", "shard_mismatch") == before + 1
    # sharded: unreadable
    p2 = tmp_path / "sharded_bad.json"
    p2.write_text("][")
    monkeypatch.setenv("RAFT_TPU_TUNE_SHARDED", str(p2))
    ts._TUNED_SHARDED = ...
    before = _degraded("sharded", "unreadable")
    assert ts.sharded_config(4) == {}
    assert _degraded("sharded", "unreadable") == before + 1


def test_table_degraded_warns_once(caplog, _fresh_tables):
    import logging

    from raft_tpu.tune.fused import (_reset_degraded_warnings,
                                     table_degraded)

    _reset_degraded_warnings()
    with caplog.at_level(logging.WARNING, logger="raft_tpu"):
        table_degraded("unit", "invalid", "first")
        table_degraded("unit", "invalid", "second")
    warns = [r for r in caplog.records
             if "degraded to built-ins" in r.getMessage()]
    assert len(warns) == 1


def test_plan_cache_injected_corrupt_read(tmp_path, monkeypatch):
    from raft_tpu.sparse import plan_cache

    monkeypatch.setenv("RAFT_TPU_TILE_PLAN_CACHE", str(tmp_path))
    fp = "deadbeef" * 4
    assert plan_cache.save_plan(fp, {"a": np.arange(4)})
    assert plan_cache.load_plan(fp) is not None
    resilience.configure_faults("plan_cache_read:corrupt")
    assert plan_cache.load_plan(fp) is None      # honest miss, no raise
    resilience.clear_faults()
    assert plan_cache.load_plan(fp) is not None


# ------------------------------------------------------------------
# perf-evidence guard: degraded runs never gate / baseline
# ------------------------------------------------------------------

def test_bench_report_refuses_degraded_evidence():
    import tools.bench_report as br

    base = {"metric": "knn 2048x1M", "unit": "GB/s", "value": 100.0}
    clean = {"metric": "knn 2048x1M", "unit": "GB/s", "value": 101.0}
    status, _ = br.check_regression(clean, base)
    assert status == br.PASS
    degraded = dict(clean, resilience_degradations=2.0)
    status, msg = br.check_regression(degraded, base)
    assert status == br.SKIP and "degrad" in msg
    rounds = [(1, "MULTICHIP_r01.json",
               {"ok": True, "measured": True, "value": 50.0,
                "unit": "GB/s", "resilience_degradations": 1.0})]
    status, msg = br.check_multichip(rounds)
    assert status == br.SKIP and "degrad" in msg


def test_fixture_stamps_degradations():
    from raft_tpu.benchmark import Fixture
    from raft_tpu.resilience import record_degradation

    fx = Fixture(reps=1, warmup=0)
    r = fx.run(lambda a: a + 1, jnp.ones(8), name="resil_fixture")
    base = r.get("resilience_degradations", 0.0)
    record_degradation("unit.fixture", "test:step")
    r2 = fx.run(lambda a: a + 1, jnp.ones(8), name="resil_fixture")
    assert r2["resilience_degradations"] >= base + 1.0
