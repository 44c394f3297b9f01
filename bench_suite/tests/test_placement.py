"""Where a cell's data lives, on eight virtual CPU devices:

- a configuration without a ``placement`` key draws the very data it
  drew before row-sharded placement existed (checksums recorded then);
- a row-sharded base is the same, bit for bit, over 1, 2, 4 and 8
  devices, each device holding exactly its own rows, and its pool is the
  one-device placement's pool;
- the reference and the true distances over a row-sharded base equal
  those over the same base on one device;
- the kernel roofline reader divides the base's work over the chips;
- a tiny four-device row-sharded cell runs through the harness's
  ``measure`` and ``report``, and its check fails a stand-in that
  misses a device's rows."""

import hashlib

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from bench_suite import load, reference, run, spec, trace
from bench_suite.tests import test_trace, tiny
from bench_suite.tools import shard_probe

SEED = 2 ** 32 + 977
N_ROWS = 4096

#: sha256 of (base, pool) from ``make_data(seed, _data())``, recorded on
#: the CPU at the commit before the ``placement`` key existed
PARENT_SHA256 = {
    7: ("c9592889ba0b4a45274683ab25fa4159eec9d48f0d2843a0777d43019921a54d",
        "dcf7aea01b7a29df44dbd8331885c9b99cd6e2b249f7ab69b1f85e0963cfe38d"),
    2 ** 33 + 5: (
        "c9592889ba0b4a45274683ab25fa4159eec9d48f0d2843a0777d43019921a54d",
        "14589c3db289633b677b4ab3e9ca669e66d3c7e2ce5574a36c3ff9ef53feb757"),
}


def _data(**extra) -> dict:
    return dict(spec.load_config("sift1m-exact")["data"], n_rows=N_ROWS,
                n_pool=512, n_centers=32, **extra)


SHARDED = _data(placement=reference.ROW_SHARDED)


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """Row blocks of 256 rows, so that the tiny base has 16."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "ROW_BLOCK", 256)
        yield


def _sha(a) -> str:
    return hashlib.sha256(np.asarray(a).tobytes()).hexdigest()


@pytest.mark.parametrize("seed", sorted(PARENT_SHA256))
def test_one_device_data_unchanged(seed):
    base, pool = reference.make_data(seed, _data())
    assert (_sha(base), _sha(pool)) == PARENT_SHA256[seed]
    # the cell's devices are not read without the key
    base4, pool4 = reference.make_data(seed, _data(), jax.devices()[:4])
    assert base4.sharding.device_set == {jax.devices()[0]}
    assert (_sha(base4), _sha(pool4)) == PARENT_SHA256[seed]


@pytest.fixture(scope="module")
def whole():
    """The row-sharded configuration's base and pool on one device."""
    base, pool = reference.make_data(SEED, SHARDED, jax.devices()[:1])
    return np.asarray(base), np.asarray(pool)


@pytest.mark.parametrize("n_dev", [1, 2, 4, 8])
def test_row_sharded_generation(n_dev, whole):
    devs = jax.devices()[:n_dev]
    base, pool = reference.make_data(SEED, SHARDED, devs)
    assert base.shape == (N_ROWS, 128) and base.dtype == np.float32
    assert base.sharding.spec == P("rows")
    assert list(base.sharding.mesh.devices) == devs
    share = N_ROWS // n_dev
    assert len(base.addressable_shards) == n_dev
    for s in base.addressable_shards:
        j = devs.index(s.device)
        assert s.index[0].indices(N_ROWS)[:2] == (j * share, (j + 1) * share)
        np.testing.assert_array_equal(np.asarray(s.data),
                                      whole[0][j * share:(j + 1) * share])
    assert pool.sharding.is_fully_replicated
    assert pool.sharding.device_set == set(devs)
    np.testing.assert_array_equal(np.asarray(pool), whole[1])


def test_row_blocks_and_pool(whole):
    """Each block is ``reference.row_block`` drawn alone; the pool is
    the draw the one-device placement makes from the same seed; the rows
    follow the same distribution as the one-device placement's."""
    for b in range(N_ROWS // 256):
        np.testing.assert_array_equal(
            np.asarray(reference.row_block(SHARDED, b, jax.devices()[3])),
            whole[0][b * 256:(b + 1) * 256])
    base1, pool1 = (np.asarray(a) for a in reference.make_data(SEED,
                                                               _data()))
    np.testing.assert_array_equal(whole[1], pool1)
    assert not np.array_equal(whole[0], base1)
    assert abs(whole[0].std() - base1.std()) < 0.02 * base1.std()


@pytest.mark.parametrize("change", [
    {"n_rows": 3 * 256}, {"n_rows": N_ROWS + 4}, {"n_rows": 0},
    {"placement": "column_sharded"}])
def test_uneven_or_unknown_placement_is_refused(change):
    with pytest.raises(ValueError):
        reference.make_data(SEED, dict(SHARDED, **change), jax.devices()[:4])


@pytest.mark.parametrize("precision", ["highest", "bf16x3"])
def test_reference_on_a_sharded_base(precision, whole, monkeypatch):
    # blocks of one device's share, so both sides score the same blocks
    monkeypatch.setattr(reference, "BASE_BLOCK", N_ROWS // 4)
    sharded, pool = reference.make_data(SEED, SHARDED, jax.devices()[:4])
    one = jax.device_put(whole[0], jax.devices()[0])
    queries = pool[:500]
    d4, i4 = reference.exact_topk(queries, sharded, 100, precision)
    d1, i1 = reference.exact_topk(jax.device_put(whole[1][:500]), one, 100,
                                  precision)
    np.testing.assert_array_equal(i4, i1)
    np.testing.assert_array_equal(d4, d1)
    # ids from every device's rows, out-of-range ids give +inf
    ids = np.concatenate([i1[:, :10], (np.arange(500) * 5)[:, None],
                          np.full((500, 1), N_ROWS), -np.ones((500, 1))],
                         axis=1)
    t4 = reference.true_distances(queries, sharded, ids)
    t1 = reference.true_distances(jax.device_put(whole[1][:500]), one, ids)
    np.testing.assert_array_equal(t4, t1)
    assert np.isinf(t4[:, -2:]).all() and np.isfinite(t4[:, :-2]).all()


def _roofline(kernel_s: float, chips: int):
    summary = trace.Summary(window_s=1.0, busy_s=kernel_s,
                            kernels={"fused_l2_group_topk_packed": kernel_s},
                            modules={}, spans={}, idle_gaps=[])
    reqs = [load.Request(rid=i, start=0, rows=r, t_due=0.0)
            for i, r in enumerate([2048, 2048, 512])]
    r = run.Run(config=spec.load_config("sift1m-exact"),
                peaks=run.load_peaks("TPU v5 lite"), setup_s=1.0,
                window=load.Window(0.0, 1.0, 1.0, reqs, []), stats_delta={},
                check_values={}, trace=summary, chips=chips)
    return spec.metric_reader("fused_topk_roofline")(r)


def test_roofline_by_chip_count():
    # the synthetic trace's 2 us of kernel, read at the parent commit
    assert _roofline(2e-6, 1) == 150082.44274809162
    # at SIFT-1M the operations bound it: a base split evenly over four
    # chips, each busy a quarter as long, reads the same share
    assert _roofline(0.5e-6, 4) == pytest.approx(_roofline(2e-6, 1),
                                                 rel=1e-12)
    recorded = trace.reduce(test_trace.RECORDED)
    assert not any(k.startswith("fused_l2_") for k in recorded.kernels)


class _ExactStandIn:
    """A plain exact system over whatever base it is given: the
    reference itself."""

    def __init__(self, cfg, base, pool, max_rows):
        self.k = int(cfg["k"])
        self.base = base
        pool = np.asarray(pool)
        self.pool = np.concatenate([pool, pool[:max_rows]])

    def submit(self, start, rows):
        return reference.exact_topk(self.pool[start:start + rows],
                                    self.base, self.k)

    def wait(self, handle, timeout):
        return handle

    def stats(self):
        return {}

    def close(self):
        self.base = None


class _FirstDeviceOnly(_ExactStandIn):
    """The stand-in broken underneath: it scans the first device's rows
    alone."""

    def __init__(self, cfg, base, pool, max_rows):
        super().__init__(cfg, base, pool, max_rows)
        self.base = reference.row_shards(base)[0][1]


def _sharded_cell() -> spec.Cell:
    cell = tiny.shrink(spec.find_cell("sift1m-exact.b2048"), n_rows=N_ROWS)
    cell.config["data"]["placement"] = reference.ROW_SHARDED
    cell.chips = 4
    return cell


@pytest.fixture(scope="module")
def synthetic():
    return trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(
        test_trace.SYNTHETIC))


@pytest.mark.parametrize("traced", [False, True])
def test_row_sharded_cell_rehearsal(traced, synthetic):
    cell = _sharded_cell()
    devices = jax.devices()[:cell.chips]
    m = run.measure(cell, SEED, 1.0, False, devices,
                    system_factory=_ExactStandIn)
    assert m.base.sharding.spec == P("rows")
    assert m.base.sharding.device_set == set(devices)
    if traced:
        m.trace = synthetic
    out, ans, _ = run.report(m, devices, tiny.CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0 and len(ans.rows)
    entries = cell.per_layer if traced else cell.end_to_end
    assert set(out["metrics"]) == {e["name"] for e in entries}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    if traced:
        least = sum(
            max(2.0 * r.rows * N_ROWS * 128 / 4 / 2e12,
                (N_ROWS * 128 / 4 + 4.0 * r.rows * 128 + 8.0 * r.rows
                 * cell.config["k"]) / 1e11)
            for r in m.window.requests)
        kernel = synthetic.kernels["fused_l2_group_topk_packed"]
        assert out["metrics"]["fused_topk_roofline"]["value"] == \
            pytest.approx(100.0 * least / kernel, rel=1e-12)


def test_row_sharded_check_fails_a_missing_device():
    cell = _sharded_cell()
    devices = jax.devices()[:cell.chips]
    m = run.measure(cell, SEED, 1.0, False, devices,
                    system_factory=_FirstDeviceOnly)
    out, _, _ = run.report(m, devices, tiny.CPU_PEAKS)
    assert not out["correct"]
    assert out["checks"]["rank_gap"]["value"] > \
        cell.config["check"]["limits"]["rank_gap"]["max"]


def test_shard_probe():
    data = dict(_data(), n_rows=8 * 256)
    out = shard_probe.probe(jax.devices()[:4], data, SEED, 300, 100)
    assert out["last_block_bit_identical"] == [True] * 4
    assert [s[:2] for s in out["shard_rows"]] == [[j * 512, 512]
                                                  for j in range(4)]
    assert out["check_values"]["rank_gap"] == 0.0
    assert out["check_values"]["dist_err"] < 1e-5
    assert len(out["memory_after_check"]) == 4
