"""Observability subsystem tests: registry semantics, span→range
attribution, comms/cache/memory bridges, exporters, the disabled-mode
contract, and the satellite fixes (nvtx stack imbalance, TRACE level)."""

import json
import logging
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import raft_tpu.observability as obs
from raft_tpu.core import nvtx
from raft_tpu.core import logger as raft_logger
from raft_tpu.observability import (
    MetricsRegistry,
    NULL_METRIC,
    export_jsonl,
    export_prometheus,
    instrument,
    span,
    summary_table,
)


@pytest.fixture(autouse=True)
def clean_registry():
    """Each test sees an empty process-global registry (other suites may
    have recorded spans already) and leaves it enabled."""
    obs.reset()
    obs.enable()
    yield
    obs.reset()
    obs.enable()


# ---------------------------------------------------------------- registry
def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", {"k": "a"})
    c.inc()
    c.inc(2.5)
    assert c.value == 3.5
    # same (name, labels) → same object; different labels → different
    assert reg.counter("c_total", {"k": "a"}) is c
    assert reg.counter("c_total", {"k": "b"}) is not c
    with pytest.raises(ValueError):
        c.inc(-1)


def test_gauge_semantics():
    reg = MetricsRegistry()
    g = reg.gauge("g")
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == 13


def test_histogram_semantics():
    reg = MetricsRegistry()
    h = reg.histogram("h", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count == 4
    assert h.sum == pytest.approx(55.55)
    assert h.bucket_counts() == [1, 1, 1, 1]
    assert h.cumulative_counts() == [1, 2, 3, 4]


def test_kind_collision_raises():
    reg = MetricsRegistry()
    reg.counter("m")
    with pytest.raises(ValueError):
        reg.gauge("m")


def test_disabled_registry_is_null_and_empty():
    reg = MetricsRegistry(enabled=False)
    c = reg.counter("nope")
    assert c is NULL_METRIC
    c.inc()
    reg.histogram("h").observe(1.0)
    reg.emit({"type": "x"})
    assert len(reg) == 0
    assert len(reg.events) == 0
    assert export_prometheus(reg) == ""


# ------------------------------------------------------------------- spans
def test_span_attributes_to_enclosing_range():
    with nvtx.annotate("outer"):
        with span("inner.work"):
            pass
    reg = obs.get_registry()
    c = reg.counter("raft_tpu_span_calls_total",
                    {"span": "inner.work", "range": "outer"})
    assert c.value == 1


def test_instrument_records_calls_time_and_bytes():
    @instrument("test.op")
    def op(x):
        return x * 2

    x = np.ones((4, 8), np.float32)
    out = op(x)
    np.testing.assert_array_equal(np.asarray(out), x * 2)
    reg = obs.get_registry()
    labels = {"span": "test.op", "range": ""}
    assert reg.counter("raft_tpu_span_calls_total", labels).value == 1
    assert reg.counter("raft_tpu_span_bytes_in_total", labels).value == 128
    assert reg.counter("raft_tpu_span_bytes_out_total", labels).value == 128
    assert reg.histogram("raft_tpu_span_seconds", labels).count == 1
    ev = list(reg.events)[-1]
    assert ev["type"] == "span" and ev["span"] == "test.op"


def test_instrument_counts_errors_and_reraises():
    @instrument("test.err")
    def bad():
        raise RuntimeError("boom")

    with pytest.raises(RuntimeError):
        bad()
    reg = obs.get_registry()
    labels = {"span": "test.err", "range": ""}
    assert reg.counter("raft_tpu_span_errors_total", labels).value == 1
    # the stack must be balanced after the exception path
    assert nvtx.current_range() is None


def test_runtime_disable_records_nothing():
    @instrument("test.quiet")
    def op():
        return 1

    obs.disable()
    op()
    assert len(obs.get_registry()) == 0
    obs.enable()
    op()
    assert len(obs.get_registry()) > 0


def test_env_disabled_instrument_is_identity():
    """With RAFT_TPU_DISABLE_TRACING set at import, instrument() must
    return the function object unchanged (the near-zero-overhead
    contract) and a full primitive run must record zero metrics."""
    code = (
        "import numpy as np\n"
        "import raft_tpu.observability as o\n"
        "from raft_tpu.observability import instrument\n"
        "def f(): pass\n"
        "assert instrument('x')(f) is f, 'expected identity decoration'\n"
        "from raft_tpu.matrix import select_k\n"
        "select_k(None, np.random.rand(4, 64).astype(np.float32), k=3)\n"
        "assert len(o.get_registry()) == 0, 'metrics recorded while disabled'\n"
        "assert o.export_prometheus() == ''\n"
    )
    env = dict(os.environ, RAFT_TPU_DISABLE_TRACING="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr


# ------------------------------------------------------- instrumented prims
def test_select_k_records_span_and_prometheus_is_valid():
    from raft_tpu.matrix import select_k

    select_k(None, np.random.rand(4, 128).astype(np.float32), k=4)
    text = export_prometheus()
    assert 'raft_tpu_span_calls_total{range="",span="matrix.select_k"} 1' \
        in text
    # minimal exposition-format validity: TYPE precedes samples, and
    # histogram series carry _bucket/_sum/_count
    lines = text.splitlines()
    typed = {ln.split()[2] for ln in lines if ln.startswith("# TYPE")}
    assert "raft_tpu_span_seconds" in typed
    assert any(ln.startswith("raft_tpu_span_seconds_bucket{") for ln in lines)
    assert any(ln.startswith("raft_tpu_span_seconds_count{") for ln in lines)


def test_nested_primitive_attributes_to_parent_span():
    """select_k invoked under an enclosing range attributes to it."""
    from raft_tpu.matrix import select_k

    with nvtx.annotate("caller"):
        select_k(None, np.random.rand(2, 64).astype(np.float32), k=2)
    reg = obs.get_registry()
    c = reg.counter("raft_tpu_span_calls_total",
                    {"span": "matrix.select_k", "range": "caller"})
    assert c.value == 1


# ------------------------------------------------------------------- comms
def test_comms_counters_one_device_mesh():
    from jax.sharding import PartitionSpec as P

    from raft_tpu.comms import MeshComms

    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("obx",))
    comms = MeshComms("obx")

    def fn(x):
        y = comms.allreduce(x)
        z = comms.allgather(x)
        w = comms.reducescatter(z.reshape(-1))
        return y + w.sum()

    x = np.ones((4, 32), np.float32)
    jax.shard_map(fn, mesh=mesh, in_specs=(P("obx"),),
                  out_specs=P("obx"))(x)
    reg = obs.get_registry()
    for coll, nbytes in (("allreduce", 4 * 32 * 4), ("allgather", 4 * 32 * 4),
                         ("reducescatter", 4 * 32 * 4)):
        labels = {"collective": coll, "axis": "obx"}
        assert reg.counter("raft_tpu_comms_calls_total", labels).value == 1, coll
        assert reg.counter("raft_tpu_comms_bytes_total", labels).value == nbytes


# ----------------------------------------------------------- cache / memory
def test_compile_cache_hit_miss_counters():
    from raft_tpu.core.resources import CompileCache

    cc = CompileCache()
    cc.get_or_compile("a", lambda: 1)
    cc.get_or_compile("a", lambda: 2)
    cc.get_or_compile("b", lambda: 3)
    assert (cc.hits, cc.misses) == (1, 2)
    reg = obs.get_registry()
    assert reg.counter("raft_tpu_compile_cache_hits_total").value == 1
    assert reg.counter("raft_tpu_compile_cache_misses_total").value == 2


def test_memory_tracker_bridge():
    from raft_tpu.core.memory import MemoryTracker

    mt = MemoryTracker()
    mt.allocate(1000)
    mt.allocate(24)
    mt.deallocate(1000)
    reg = obs.get_registry()
    assert reg.counter("raft_tpu_memory_alloc_total").value == 2
    assert reg.counter("raft_tpu_memory_alloc_bytes_total").value == 1024
    assert reg.gauge("raft_tpu_memory_current_bytes").value == 24
    assert reg.gauge("raft_tpu_memory_peak_bytes").value == 1024


def test_resources_metrics_slot():
    from raft_tpu.core import DeviceResources, ResourceType

    res = DeviceResources()
    assert res.metrics is obs.get_registry()
    private = MetricsRegistry()
    res.set_metrics(private)
    assert res.metrics is private
    assert res.has_resource_factory(ResourceType.METRICS)


# -------------------------------------------------------------- benchmark
def test_fixture_run_emits_through_registry():
    import jax.numpy as jnp

    from raft_tpu.benchmark import Fixture

    fx = Fixture(reps=2)
    r = fx.run(lambda x: x + 1, jnp.ones((8,)), name="obs_bench")
    assert "seconds" in r
    results = obs.bench_results()
    assert "obs_bench" in results
    assert results["obs_bench"]["seconds"] == r["seconds"]
    reg = obs.get_registry()
    assert reg.histogram("raft_tpu_benchmark_seconds",
                         {"bench": "obs_bench"}).count == 1


# -------------------------------------------------------------- exporters
def _golden_registry():
    reg = MetricsRegistry()
    reg.counter("t_total", {"k": "v"}, help="a counter").inc(3)
    reg.gauge("t_gauge").set(1.5)
    reg.histogram("t_seconds", buckets=(0.1, 1.0)).observe(0.5)
    return reg


def test_prometheus_golden():
    assert export_prometheus(_golden_registry()) == (
        '# TYPE t_gauge gauge\n'
        't_gauge 1.5\n'
        '# TYPE t_seconds histogram\n'
        't_seconds_bucket{le="0.1"} 0\n'
        't_seconds_bucket{le="1"} 1\n'
        't_seconds_bucket{le="+Inf"} 1\n'
        't_seconds_sum 0.5\n'
        't_seconds_count 1\n'
        '# HELP t_total a counter\n'
        '# TYPE t_total counter\n'
        't_total{k="v"} 3\n'
    )


def test_prometheus_label_escaping():
    reg = MetricsRegistry()
    reg.counter("e_total", {"p": 'a"b\\c\nd'}).inc()
    assert 'e_total{p="a\\"b\\\\c\\nd"} 1' in export_prometheus(reg)


def test_prometheus_help_escaping():
    reg = MetricsRegistry()
    reg.counter("h_total", help="line one\nline two \\ done").inc()
    text = export_prometheus(reg)
    # HELP continuation lines escape \n and \ per the exposition
    # format — a literal newline would truncate the comment and make
    # the next line junk to the scraper
    assert "# HELP h_total line one\\nline two \\\\ done\n" in text
    assert "\nline two" not in text


def _parse_exposition(text):
    """Minimal exposition-format parser (scrape-side view): name →
    {(label tuple): value}, unescaping label values."""
    out = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, value = line.rsplit(" ", 1)
        if "{" in name_part:
            name, rest = name_part.split("{", 1)
            body = rest.rsplit("}", 1)[0]
            labels = []
            for item in _split_labels(body):
                k, v = item.split("=", 1)
                labels.append((k, _unescape(v[1:-1])))
            key = tuple(sorted(labels))
        else:
            name, key = name_part, ()
        out.setdefault(name, {})[key] = float(value)
    return out


def _unescape(v):
    """Single-pass label-value unescape (sequential str.replace would
    corrupt a literal backslash-n into a newline)."""
    out, i = [], 0
    while i < len(v):
        if v[i] == "\\" and i + 1 < len(v):
            nxt = v[i + 1]
            if nxt == "n":
                out.append("\n")
                i += 2
                continue
            if nxt in ('"', "\\"):
                out.append(nxt)
                i += 2
                continue
        out.append(v[i])
        i += 1
    return "".join(out)


def _split_labels(body):
    """Split a label body on commas OUTSIDE quoted values."""
    items, cur, in_q, esc = [], "", False, False
    for ch in body:
        if esc:
            cur += ch
            esc = False
        elif ch == "\\":
            cur += ch
            esc = True
        elif ch == '"':
            cur += ch
            in_q = not in_q
        elif ch == "," and not in_q:
            items.append(cur)
            cur = ""
        else:
            cur += ch
    if cur:
        items.append(cur)
    return items


def test_prometheus_roundtrip_adversarial_labels():
    # label values chosen to break naive exposition writers: embedded
    # quotes, backslashes, newlines, commas, braces, '=' signs
    adversarial = ['plain', 'a"b', 'back\\slash', 'new\nline',
                   'comma,brace}', 'eq=sign', '\\"both\\n', '']
    reg = MetricsRegistry()
    for i, v in enumerate(adversarial):
        reg.counter("rt_total", {"p": v, "i": str(i)}).inc(i + 1)
    parsed = _parse_exposition(export_prometheus(reg))
    assert len(parsed["rt_total"]) == len(adversarial)
    for i, v in enumerate(adversarial):
        key = tuple(sorted([("p", v), ("i", str(i))]))
        assert parsed["rt_total"][key] == i + 1, (i, v)


# -------------------------------------------------- histogram percentiles
def test_percentile_empty_histogram_is_none():
    reg = MetricsRegistry()
    h = reg.histogram("p_seconds", buckets=(0.1, 1.0))
    assert h.percentile(50) is None
    assert h.percentile(99) is None


def test_percentile_single_bucket_interpolates_from_zero_edge():
    reg = MetricsRegistry()
    h = reg.histogram("p1_seconds", buckets=(1.0,))
    for _ in range(4):
        h.observe(0.5)
    # all mass in [0, 1]: rank interpolation within the first bucket,
    # lower edge pinned at min(0, b0) = 0
    assert h.percentile(50) == pytest.approx(0.5)
    assert h.percentile(100) == pytest.approx(1.0)


def test_percentile_all_in_overflow_clamps_to_last_bound():
    reg = MetricsRegistry()
    h = reg.histogram("p2_seconds", buckets=(0.1, 1.0))
    for _ in range(10):
        h.observe(50.0)                  # everything past the buckets
    # +Inf bucket has no upper edge — the estimate clamps to the last
    # FINITE bound rather than inventing a number
    assert h.percentile(50) == pytest.approx(1.0)
    assert h.percentile(99) == pytest.approx(1.0)


def test_percentile_negative_first_edge():
    from raft_tpu.observability.metrics import bucket_percentile

    # a bucket layout spanning negatives (the certificate-margin
    # histogram): the first bucket's lower edge is min(0, b0)
    buckets = (-10.0, -1.0, 0.0, 1.0)
    cumulative = [4, 4, 4, 4, 4]         # all mass in (-inf, -10]
    assert bucket_percentile(buckets, cumulative, 50) <= -5.0


def test_jsonl_golden():
    reg = _golden_registry()
    reg.emit({"type": "span", "span": "s", "range": "", "seconds": 0.25,
              "bytes_in": 1, "bytes_out": 2, "error": False, "ts": 0.0})
    lines = export_jsonl(reg).strip().split("\n")
    recs = [json.loads(ln) for ln in lines]
    assert recs[0] == {"type": "span", "span": "s", "range": "",
                       "seconds": 0.25, "bytes_in": 1, "bytes_out": 2,
                       "error": False, "ts": 0.0}
    by_name = {r["name"]: r for r in recs[1:]}
    assert by_name["t_total"] == {"type": "metric", "name": "t_total",
                                  "labels": {"k": "v"}, "kind": "counter",
                                  "value": 3.0}
    assert by_name["t_seconds"]["bucket_counts"] == [0, 1, 0]


def test_summary_table_renders():
    out = summary_table(_golden_registry())
    assert "t_total" in out and "count=1" in out
    assert summary_table(MetricsRegistry()).startswith("(no metrics")


# ------------------------------------------------- satellite: nvtx stack
def test_nvtx_exception_path_balances_stack():
    with pytest.raises(ValueError):
        with nvtx.annotate("doomed"):
            assert nvtx.current_range() == "doomed"
            raise ValueError("x")
    assert nvtx.current_range() is None
    assert nvtx.range_stack() == []


def test_nvtx_mismatch_pops_defensively_and_warns(caplog):
    nvtx.push_range("a")
    # simulate the skew a buggy caller creates: a stale name on top
    nvtx._stack().append("stale")
    with caplog.at_level(logging.WARNING, logger="raft_tpu"):
        nvtx.pop_range()   # exits entry "a", finds "stale" on top
    assert nvtx.range_stack() == ["a"]   # stale entry evicted, not stuck
    assert any("imbalance" in r.message for r in caplog.records)
    nvtx._stack().clear()  # leave no residue for other tests
    getattr(nvtx._tls, "entries", []).clear()


def test_nvtx_empty_stack_pop_warns(caplog):
    entry = nvtx._RangeEntry("ghost")
    entry._ann.__enter__()
    entry._scope.__enter__()
    with caplog.at_level(logging.WARNING, logger="raft_tpu"):
        entry.exit()
    assert any("imbalance" in r.message for r in caplog.records)
    assert nvtx.range_stack() == []


# ---------------------------------------------------- satellite: logger
def test_trace_level_is_named():
    assert logging.getLevelName(raft_logger.TRACE) == "TRACE"


def test_log_trace_renders_trace(caplog):
    with caplog.at_level(raft_logger.TRACE, logger="raft_tpu"):
        raft_logger.log_trace("hello %s", "trace")
    assert any(r.levelname == "TRACE" for r in caplog.records)


def test_raft_log_active_level_alias(monkeypatch):
    monkeypatch.delenv("RAFT_TPU_LOG_LEVEL", raising=False)
    monkeypatch.setenv("RAFT_LOG_ACTIVE_LEVEL", "RAFT_LEVEL_TRACE")
    assert raft_logger._env_level() == raft_logger.TRACE
    monkeypatch.setenv("RAFT_LOG_ACTIVE_LEVEL", "warn")
    assert raft_logger._env_level() == logging.WARNING
    # RAFT_TPU_LOG_LEVEL wins when both are set
    monkeypatch.setenv("RAFT_TPU_LOG_LEVEL", "error")
    assert raft_logger._env_level() == logging.ERROR


def test_set_level_knows_trace():
    lg = raft_logger.default_logger()
    before = lg.level
    try:
        raft_logger.set_level("trace")
        assert lg.level == raft_logger.TRACE
    finally:
        lg.setLevel(before)


# ------------------------------------------------ cost model / roofline
def _tools_import(name):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        return __import__(name)
    finally:
        sys.path.pop(0)


def test_chip_spec_cpu_fallback_and_tpu_table():
    from raft_tpu.utils import arch

    spec = arch.chip_spec()   # CPU platform under the tier-1 suite
    assert spec is arch.CPU_SPEC
    assert spec.ridge == spec.peak_flops / spec.hbm_bw
    # table entries: the v5e row is the chip the round-5 verdict's
    # 460-vs-819 GB/s gap is measured against
    v5e = arch.TPU_SPECS[(5, "e")]
    assert v5e.hbm_bw == pytest.approx(819e9)
    assert v5e.ridge > 100  # TPUs: heavily compute-biased ridge


class _Dev:
    def __init__(self, kind):
        self.device_kind = kind


@pytest.mark.parametrize("kind,name", [("TPU v5 lite", "tpu v5e"),
                                       ("TPU v5e", "tpu v5e"),
                                       ("TPU v5p", "tpu v5p"),
                                       ("TPU v4", "tpu v4")])
def test_chip_spec_resolves_known_tpu_kinds(kind, name):
    from raft_tpu.utils import arch

    assert arch.chip_spec(_Dev(kind)).name == name


@pytest.mark.parametrize("kind", ["TPU v7x", "TPU v9 ultra", "TPU"])
def test_chip_spec_refuses_unknown_tpu_kinds(kind):
    """No nearest-generation guess: a roofline share against another
    chip's peaks would be a wrong number."""
    from raft_tpu.utils import arch

    with pytest.raises(ValueError, match="TPU_SPECS"):
        arch.chip_spec(_Dev(kind))


def test_cost_capture_pairwise_distance():
    import jax.numpy as jnp

    from raft_tpu.distance import pairwise_distance
    from raft_tpu.observability.profiler import Profiler

    prof = Profiler()
    x = jnp.asarray(np.random.rand(32, 16).astype(np.float32))
    y = jnp.asarray(np.random.rand(24, 16).astype(np.float32))
    rec = prof.capture_fn("pairwise_distance",
                          lambda a, b: pairwise_distance(None, a, b), x, y)
    assert rec is not None
    assert rec.flops > 0
    assert rec.bytes_accessed > 0
    # the capture published into the registry: gauge + cost event
    reg = obs.get_registry()
    assert reg.gauge("raft_tpu_cost_flops",
                     {"entry": "pairwise_distance"}).value == rec.flops
    assert any(ev.get("type") == "cost" and
               ev.get("entry") == "pairwise_distance"
               for ev in reg.events)
    # memoized: same signature → same record, no second analysis compile
    assert prof.capture_fn("pairwise_distance",
                           lambda a, b: pairwise_distance(None, a, b),
                           x, y) is rec


def test_cost_capture_select_k_and_tiled_spmv():
    import jax.numpy as jnp

    from raft_tpu.core.sparse_types import CSRMatrix
    from raft_tpu.matrix import select_k
    from raft_tpu.observability.costmodel import MEMORY_BOUND, classify
    from raft_tpu.observability.profiler import Profiler
    from raft_tpu.sparse.linalg import spmv
    from raft_tpu.sparse.tiled import tile_csr

    prof = Profiler()
    a = jnp.asarray(np.random.rand(8, 256).astype(np.float32))
    rec = prof.capture_fn("select_k", lambda v: select_k(None, v, k=8), a)
    assert rec is not None and rec.bytes_accessed > 0

    rng = np.random.default_rng(0)
    dense = (rng.random((256, 256))
             * (rng.random((256, 256)) < 0.1)).astype(np.float32)
    tiled = tile_csr(CSRMatrix.from_dense(dense), C=128, R=8, E=512)
    xv = jnp.asarray(rng.random(256), jnp.float32)
    rec2 = prof.capture_fn("spmv_tiled", lambda t, v: spmv(None, t, v),
                           tiled, xv)
    assert rec2 is not None and rec2.bytes_accessed > 0
    assert prof.get("spmv_tiled") is rec2
    # SpMV streams its operand once: memory-bound on any spec table entry
    assert classify(rec2.arithmetic_intensity, prof.spec) == MEMORY_BOUND


def test_roofline_classification_sanity():
    """GEMM → compute-bound, SpMV-like streaming → memory-bound, on the
    deterministic CPU fallback peaks."""
    import jax.numpy as jnp

    from raft_tpu.observability import costmodel
    from raft_tpu.observability.profiler import Profiler
    from raft_tpu.utils.arch import CPU_SPEC

    prof = Profiler(spec=CPU_SPEC)
    n = 256
    a = jnp.ones((n, n), jnp.float32)
    gemm = prof.capture_fn("gemm", jax.jit(lambda p, q: p @ q), a, a)
    assert gemm is not None
    # AI ≈ n/6 = 42.7 FLOP/B >> ridge 8
    assert costmodel.classify(gemm.arithmetic_intensity, CPU_SPEC) \
        == costmodel.COMPUTE_BOUND
    v = jnp.ones((1 << 18,), jnp.float32)
    axpy = prof.capture_fn("axpy", jax.jit(lambda p: p * 2.0 + 1.0), v)
    assert axpy is not None
    assert costmodel.classify(axpy.arithmetic_intensity, CPU_SPEC) \
        == costmodel.MEMORY_BOUND
    # roofline estimate math: utilization in (0, 1], roof time positive
    est = costmodel.roofline(gemm, CPU_SPEC, seconds=1.0)
    assert est.bound == costmodel.COMPUTE_BOUND
    assert est.roof_seconds > 0
    assert 0 < est.utilization <= 1


def test_fixture_run_emits_cost_model_fields():
    import jax.numpy as jnp

    from raft_tpu.benchmark import Fixture

    fx = Fixture(reps=2)
    f = jax.jit(lambda p, q: p @ q)
    a = jnp.ones((128, 128), jnp.float32)
    r = fx.run(f, a, a, name="obs_cost_bench")
    for field in ("flops", "bytes_accessed", "arithmetic_intensity",
                  "peak_hbm_bytes", "bound", "roofline_frac"):
        assert field in r, field
    assert r["flops"] > 0 and r["bytes_accessed"] > 0
    assert r["bound"] in ("compute-bound", "memory-bound")
    assert 0 < r["roofline_frac"] <= 1
    # the benchmark event (the BENCH_*.json substrate) carries them too
    ev = obs.bench_results()["obs_cost_bench"]
    assert ev["flops"] == r["flops"]
    assert ev["bound"] == r["bound"]


def test_roofline_report_instrumented_hot_paths():
    """Acceptance: a CPU run of instrumented hot paths produces a
    roofline_report with per-primitive FLOPs, bytes, AI, and bound."""
    import jax.numpy as jnp

    from raft_tpu.benchmark import Fixture
    from raft_tpu.distance import pairwise_distance
    from raft_tpu.matrix import select_k
    from raft_tpu.observability import roofline_report

    fx = Fixture(reps=1)
    x = jnp.asarray(np.random.rand(64, 32).astype(np.float32))
    y = jnp.asarray(np.random.rand(48, 32).astype(np.float32))
    fx.run(lambda a, b: pairwise_distance(None, a, b), x, y,
           name="pairwise_distance")
    fx.run(lambda v: select_k(None, v, k=8)[0],
           jnp.asarray(np.random.rand(16, 512).astype(np.float32)),
           name="matrix.select_k")
    out = roofline_report()
    assert "pairwise_distance" in out and "matrix.select_k" in out
    for col in ("flops", "bytes", "AI", "bound", "%roof"):
        assert col in out
    assert "bound" in out and ("memory-bound" in out
                               or "compute-bound" in out)


def test_aot_call_captures_cost():
    import jax.numpy as jnp

    from raft_tpu.core.resources import DeviceResources
    from raft_tpu.observability.profiler import Profiler
    from raft_tpu.runtime.entry_points import _aot_call

    res = DeviceResources()
    res.set_profiler(Profiler())
    out = _aot_call(res, "aot_double", (), lambda v: v * 2.0,
                    jnp.ones((64,), jnp.float32))
    np.testing.assert_allclose(np.asarray(out), 2.0)
    rec = res.profiler.get("aot_double")
    assert rec is not None
    assert rec.bytes_accessed > 0
    assert rec.key  # shape+sharding signature recorded
    # cache hit: no re-capture needed, record survives
    _aot_call(res, "aot_double", (), lambda v: v * 2.0,
              jnp.ones((64,), jnp.float32))
    assert res.profiler.get("aot_double") is rec


def test_resources_profiler_slot():
    from raft_tpu.core import DeviceResources
    from raft_tpu.observability.profiler import Profiler, get_profiler

    res = DeviceResources()
    p = res.profiler
    assert isinstance(p, Profiler)
    assert res.profiler is p          # lazily built once, then cached
    mine = Profiler()
    res.set_profiler(mine)
    assert res.profiler is mine
    # the process-global fallback exists and is a Profiler too
    assert isinstance(get_profiler(), Profiler)


def test_profiler_trace_bridges_range_stack():
    from raft_tpu.observability.profiler import Profiler

    prof = Profiler()
    with nvtx.annotate("outer.phase"):
        with prof.trace(name="trace.window"):
            pass
    reg = obs.get_registry()
    c = reg.counter("raft_tpu_span_calls_total",
                    {"span": "trace.window", "range": "outer.phase"})
    assert c.value == 1
    assert nvtx.current_range() is None  # balanced on exit


# ------------------------------------------------------- bench_report
def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)


def _bench_dir(tmp_path, latest_value, baseline_value=460.0,
               with_baseline=True, degraded=False, unit="GB/s"):
    metric = "fused top-64 2048x1000000x128"
    _write(tmp_path / "BENCH_r01.json",
           {"n": 1, "parsed": {"metric": metric, "value": 100.0,
                               "unit": unit, "git_commit": "aaa"}})
    _write(tmp_path / "BENCH_r02.json",
           {"n": 2, "parsed": {"metric": metric + " (tpu)",
                               "value": latest_value, "unit": unit,
                               "degraded": degraded,
                               "git_commit": "bbb"}})
    if with_baseline:
        _write(tmp_path / "BENCH_LAST_GOOD.json",
               {"metric": metric, "value": baseline_value, "unit": unit})
    return str(tmp_path)


def test_bench_report_trajectory_and_pass(tmp_path, capsys):
    br = _tools_import("bench_report")
    d = _bench_dir(tmp_path, latest_value=470.0)
    rounds = br.collect_rounds(d)
    assert [n for n, _, _ in rounds] == [1, 2]
    out = br.trajectory(rounds, br.load_record(
        os.path.join(d, "BENCH_LAST_GOOD.json")))
    assert "r01" in out and "r02" in out and "LAST_GOOD" in out
    assert br.main(["--dir", d, "--check"]) == 0
    assert "pass" in capsys.readouterr().out


def test_bench_report_detects_regression(tmp_path, capsys):
    br = _tools_import("bench_report")
    # 300 GB/s vs 460 last-good: −35% >> 15% threshold
    d = _bench_dir(tmp_path, latest_value=300.0)
    assert br.main(["--dir", d, "--check"]) == 1
    assert "REGRESSION" in capsys.readouterr().out
    # generous threshold: passes again
    assert br.main(["--dir", d, "--check", "--threshold", "0.5"]) == 0


def test_bench_report_missing_baseline(tmp_path, capsys):
    br = _tools_import("bench_report")
    d = _bench_dir(tmp_path, latest_value=300.0, with_baseline=False)
    assert br.main(["--dir", d, "--check"]) == 2
    assert "missing-baseline" in capsys.readouterr().out


def test_bench_report_skips_degraded_and_empty(tmp_path, capsys):
    br = _tools_import("bench_report")
    # degraded latest → no-op exit 0 even though the value regressed
    d = _bench_dir(tmp_path, latest_value=1.0, degraded=True)
    assert br.main(["--dir", d, "--check"]) == 0
    # seconds-style unit: regression is UPWARD
    d2 = tmp_path / "ms"
    d2.mkdir()
    _write(d2 / "BENCH_r01.json",
           {"parsed": {"metric": "op", "value": 30.0, "unit": "ms"}})
    _write(d2 / "BENCH_LAST_GOOD.json",
           {"metric": "op", "value": 20.0, "unit": "ms"})
    assert br.main(["--dir", str(d2), "--check"]) == 1
    # empty dir → nothing to gate
    d3 = tmp_path / "empty"
    d3.mkdir()
    assert br.main(["--dir", str(d3), "--check"]) == 0
    capsys.readouterr()


def test_bench_report_check_on_repo_is_noop():
    """The tier-1 wiring: ``bench_report.py --check`` on the repo's real
    artifacts must exit 0 (no new gateable artifact → no-op) — the same
    invocation CI runs."""
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bench_report.py"),
         "--check"], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_bench_report_trajectory_over_rounds(tmp_path):
    """Acceptance: the CLI prints a trajectory over BENCH_r01..r05.json
    and the last-good baseline (built here: the repo keeps no chip
    headline yet)."""
    metric = "fused top-64 2048x1000000x128"
    for n in range(1, 6):
        _write(tmp_path / f"BENCH_r0{n}.json",
               {"n": n, "parsed": {"metric": metric, "value": 100.0 + n,
                                   "unit": "GB/s"}})
    _write(tmp_path / "BENCH_LAST_GOOD.json",
           {"metric": metric, "value": 104.0, "unit": "GB/s"})
    root = os.path.join(os.path.dirname(__file__), "..")
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "bench_report.py"),
         "--dir", str(tmp_path)], capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr
    for tag in ("r01", "r05", "LAST_GOOD"):
        assert tag in proc.stdout


# ------------------------------------------------------- static checker
def test_cost_capture_sites_checked(tmp_path):
    ci = _tools_import("check_instrumented")
    assert ci.check_cost_capture() == []
    mod = tmp_path / "bench_like.py"
    mod.write_text("def run():\n    return 1\n")
    errors = ci.check_cost_capture(
        root=str(tmp_path), sites={"bench_like.py": ("capture_fn",)})
    assert len(errors) == 1 and "capture_fn" in errors[0]


def test_hot_paths_are_instrumented():
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import check_instrumented
    finally:
        sys.path.pop(0)
    errors = check_instrumented.check()
    assert errors == []


def test_checker_catches_missing_instrumentation(tmp_path):
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
    try:
        import check_instrumented
    finally:
        sys.path.pop(0)
    mod = tmp_path / "raw.py"
    mod.write_text("def hot(x):\n    return x\n")
    errors = check_instrumented.check(
        root=str(tmp_path), hot_paths={"raw.py": ("hot",)})
    assert len(errors) == 2  # missing import + undecorated function
    assert any("not decorated" in e for e in errors)
