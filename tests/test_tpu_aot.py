"""Compile the main-path Pallas kernels for a DESCRIBED TPU v5e at the
chip_smoke.py shapes (SIFT-1M: 1M x 128 f32, L2) — no chip needed.

The TPU compiler is installed in the CPU sandbox and compiles for a
topology that is described but not attached: it refuses what interpret
mode cannot see (unaligned slices, scoped-VMEM overruns). Nothing runs,
so these tests say nothing about results or times.

This is the ONLY test file that describes a topology, and it does so in
a module-scoped fixture (never at import, in ``skipif`` or in
``parametrize``): only one process at a time may load libtpu, and the
xdist worker given this file keeps it until it exits.
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# SIFT-1M shape (chip_smoke.py): 2^20 rows x 128 f32, k=64 exact
N_ROWS, DIM, K_EXACT, N_QUERIES = 1 << 20, 128, 64, 2048
# served IVF-Flat / IVF-PQ: 1024 lists; a list-major chunk of queries
N_LISTS, WK, NQP, LP = 1024, 2048, 16, 512
# the IVF-Flat list-major schedule length: the SIFT-1M index's most
# entries (≈ 1,100 windows), in whole 8-entry cells
SCHED = 138 * 8
# IVF-PQ codes: pq_dim=32 subspaces of 8 bits
PQ_DIM, PQ_BITS = 32, 8

_KERNEL_MODULES = ("fused_l2_topk_pallas", "fine_scan_pallas",
                   "pq_scan_pallas", "select_slotted_pallas",
                   "spmv_pallas")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def chip(topo, monkeypatch):
    """One described v5e chip with the kernels steered to Mosaic and
    the persistent compile cache off (a described-device compile is
    written to it but can never be read back without a chip)."""
    import importlib

    from jax.experimental.compilation_cache import compilation_cache

    for name in _KERNEL_MODULES:
        mod = importlib.import_module(f"raft_tpu.ops.{name}")
        monkeypatch.setattr(mod, "interpret_mode", lambda: False)
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args, **static):
    lowered = jax.jit(functools.partial(fn, **static)).lower(*args)
    text = lowered.compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


@pytest.mark.parametrize("T,Qb", [(512, 128), (1024, 256), (2048, 256)])
def test_fused_l2_group_topk_packed_tiles_compile(chip, T, Qb):
    """The packed streamed kernel the exact path runs, at tiles other
    than the pipeline test's: 8 tiles a group keeps every (T, Qb) here
    inside the 8-bit packing envelope (8 · T/128 ≤ 256)."""
    from raft_tpu.ops.fused_l2_topk_pallas import fused_l2_group_topk_packed

    def run(x, y_hi, y_lo, yy_half, m_real, xxh):
        return fused_l2_group_topk_packed(x, y_hi, y_lo, yy_half, m_real,
                                          T=T, Qb=Qb, passes=3, tpg=8,
                                          stream=True, xxh=xxh)

    s = functools.partial(_spec, chip)
    _compile(run, s((N_QUERIES, DIM), jnp.float32),
             s((N_ROWS, DIM), jnp.bfloat16), s((N_ROWS, DIM), jnp.bfloat16),
             s((8, N_ROWS), jnp.float32), s((1,), jnp.int32),
             s((N_QUERIES, 1), jnp.float32))


def test_knn_fused_selected_pipeline_compiles(chip):
    """The whole certified pipeline that ``distance.knn`` runs on a
    prepared 1M x 128 index at k=64 (the tuned/fitted geometry
    prepare_knn_index picks, its packed kernel and the rescore)."""
    from raft_tpu.distance import knn_fused as kf

    held = {}

    def prepare(y):
        idx = kf.prepare_knn_index(y)
        held["idx"] = idx
        return idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw

    ops = jax.eval_shape(prepare, jax.ShapeDtypeStruct((N_ROWS, DIM),
                                                       jnp.float32))
    idx = held["idx"]
    Qb = min(idx.Qb, N_QUERIES)
    packed = idx.g * (idx.T // 128) <= (1 << idx.pbits)
    assert packed, "the SIFT-1M geometry must take the packed kernel"
    args = [_spec(chip, (N_QUERIES, DIM), jnp.float32)] + [
        None if o is None else _spec(chip, o.shape, o.dtype) for o in ops]
    _compile(kf._knn_fused_core, *args, k=K_EXACT, T=idx.T, Qb=Qb,
             g=idx.g, passes=idx.passes, metric="l2", m=N_ROWS,
             rescore=True, pbits=idx.pbits, grid_order=idx.grid_order,
             with_stats=True)


def test_chip_share_fixup_holds_one_tile(chip):
    """One chip's share of the four-chip exact cell (8 x 2^20 rows,
    a 512-row micro-batch, k=100) compiles with its certificate fixup
    selecting through the threshold block filter, and holds the 16-row
    distance tile once: a relayout of the tile, or an exact fallback
    that sorts it whole, shows as temporary memory past one tile."""
    from raft_tpu.distance import knn_fused as kf

    rows, q, k = 8 * N_ROWS, 512, 100
    held = {}

    def prepare(y):
        idx = kf.prepare_knn_index(y)
        held["idx"] = idx
        return idx.yp, idx.y_hi, idx.y_lo, idx.yyh_k, idx.yy_raw

    ops = jax.eval_shape(prepare, jax.ShapeDtypeStruct((rows, DIM),
                                                       jnp.float32))
    idx = held["idx"]
    M = ops[3].shape[1]
    assert kf.fixup_tiers_for(M) == (16,)
    assert kf.fixup_filter_geometry(M, k) is not None
    args = [_spec(chip, (q, DIM), jnp.float32)] + [
        _spec(chip, o.shape, o.dtype) for o in ops]
    compiled = jax.jit(functools.partial(
        kf._knn_fused_core, k=k, T=idx.T, Qb=min(idx.Qb, q), g=idx.g,
        passes=idx.passes, metric="l2", m=rows, rescore=True,
        pbits=idx.pbits, grid_order=idx.grid_order,
        with_stats=True)).lower(*args).compile()
    tile = 16 * M * 4
    assert compiled.memory_analysis().temp_size_in_bytes < 1.1 * tile


@pytest.mark.parametrize("nqp", [NQP, 32, 64, 128, 256])
@pytest.mark.parametrize("q8", [False, True])
def test_fine_scan_list_major_compiles(chip, nqp, q8):
    """The list-major kernel at each chunk a served search reaches: a
    1-row request padded to the 32-row bucket, the 128-row bucket, and
    the 256-row chunks of the 512-row bucket (the largest the footprint
    estimate admits at this window), over a schedule padded to the
    index's longest with a traced cell count."""
    from raft_tpu.ops.fine_scan_pallas import (fine_scan_list_major,
                                               fine_scan_list_major_q8,
                                               fine_scan_vmem_footprint)
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget

    assert fine_scan_vmem_footprint(WK, nqp, DIM, q8) <= vmem_budget()
    s = functools.partial(_spec, chip)
    rows = N_ROWS + N_LISTS * 8
    query = (s((nqp, DIM), jnp.float32), s((nqp, 1), jnp.float32),
             s((nqp, 128), jnp.int32))
    sched, n_cells = s((4, SCHED), jnp.int32), s((), jnp.int32)
    if q8:
        _compile(fine_scan_list_major_q8, sched, s((SCHED,), jnp.float32),
                 n_cells, *query, s((rows, DIM), jnp.int8), Wk=WK)
    else:
        _compile(fine_scan_list_major, sched, n_cells, *query,
                 s((rows, DIM), jnp.float32), Wk=WK)


@pytest.mark.parametrize("q8", [False, True])
def test_fine_scan_list_program_compiles(chip, q8):
    """The served bucket's whole list-major program: the schedule built
    on the device from the [32, 32] probe table (sort, run starts,
    searchsorted expansion to the padded schedule), the kernel with
    its traced cell count, and the exact rescore and certificate."""
    from raft_tpu.ann import ivf_flat

    s = functools.partial(_spec, chip)
    rows = N_ROWS + N_LISTS * 8
    nq, P, W = 32, 32, 2768
    lists = functools.partial(s, (N_LISTS,))
    common = dict(k=10, P=P, W=W, Wk=WK, n=SCHED)
    x, probes = s((nq, DIM), jnp.float32), s((nq, P), jnp.int32)
    slab, ids = s((rows, DIM), jnp.float32), s((rows,), jnp.int32)
    yy, offsets = s((rows,), jnp.float32), s((N_LISTS + 1,), jnp.int32)
    if q8:
        _compile(ivf_flat._fine_scan_list_q8, x, probes,
                 s((rows, DIM), jnp.int8), slab, ids, yy,
                 lists(jnp.float32), lists(jnp.float32),
                 lists(jnp.float32), offsets, lists(jnp.int32),
                 lists(jnp.int32), **common)
    else:
        _compile(ivf_flat._fine_scan_list, x, probes, slab, ids, yy,
                 offsets, lists(jnp.int32), lists(jnp.int32),
                 lists(jnp.float32), **common)


def test_pq_scan_list_major_compiles(chip):
    from raft_tpu.ops.fused_l2_topk_pallas import vmem_budget
    from raft_tpu.ops.pq_scan_pallas import (kernel_rows,
                                             pq_scan_list_major,
                                             pq_scan_vmem_footprint)

    K = 1 << PQ_BITS
    assert pq_scan_vmem_footprint(WK, NQP, PQ_DIM, K, LP,
                                  PQ_BITS) <= vmem_budget()
    s = functools.partial(_spec, chip)
    rows = kernel_rows(N_ROWS + N_LISTS * 8)
    _compile(pq_scan_list_major, s((4, LP), jnp.int32),
             s((NQP, 1), jnp.float32), s((NQP, 128), jnp.int32),
             s((NQP, LP), jnp.float32), s((NQP, PQ_DIM * K), jnp.float32),
             s((PQ_DIM, rows), jnp.int8), s((1, rows), jnp.float32),
             s((1, rows), jnp.float32), Wk=WK, pq_bits=PQ_BITS)


def _custom_call_names(text):
    return {line.split("=")[0].strip().lstrip("ROOT ").lstrip("%")
            .rsplit(".", 1)[0]
            for line in text.splitlines() if "custom-call(" in line}


@pytest.mark.parametrize("kernel", ["fused_l2_group_topk_packed",
                                    "fine_scan_list_major"])
def test_kernel_op_names_are_stable(chip, kernel):
    """The device trace names each kernel by its ``pallas_call`` name,
    which the benchmark's kernel metrics match by prefix
    (``fused_l2_*topk*``, ``fine_scan*``): the kernel traced under a
    Python function of any other name must keep it."""
    from raft_tpu.ops import fine_scan_pallas as fs
    from raft_tpu.ops import fused_l2_topk_pallas as fk

    s = functools.partial(_spec, chip)
    if kernel == "fine_scan_list_major":
        def some_renamed_entry(*a):
            return fs.fine_scan_list_major.__wrapped__(*a, Wk=256)

        args = (s((4, 16), jnp.int32), s((), jnp.int32),
                s((16, DIM), jnp.float32),
                s((16, 1), jnp.float32), s((16, 128), jnp.int32),
                s((4096, DIM), jnp.float32))
    else:
        def some_renamed_entry(*a):
            return fk.fused_l2_group_topk_packed.__wrapped__(
                *a, T=512, Qb=128, passes=3, tpg=2)

        M = 8192
        args = (s((128, DIM), jnp.float32), s((M, DIM), jnp.bfloat16),
                s((M, DIM), jnp.bfloat16), s((8, M), jnp.float32),
                s((1,), jnp.int32))
    assert _custom_call_names(_compile(some_renamed_entry, *args)) \
        == {kernel}
