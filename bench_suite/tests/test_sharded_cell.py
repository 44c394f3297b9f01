"""The four-chip sharded exact-KNN cell (``bigann32m-exact4.b2048``) at a
tiny size on four virtual CPU devices:

- its configuration's tiny twin (``data/bigann32m-exact4.tiny.json``)
  runs through the harness's ``measure`` and ``report`` with the real
  ``exact_knn_sharded`` system, and is correct; the same system broken
  underneath (``test_control``'s faults) is not, and neither is the
  control, the plain reference at bf16x3 put in the program's place;
- its two per-layer readers, ``shard_merge.ms`` (the merge's collective
  ops in the device trace) and ``shard_fixup.share`` (the certificate
  counters the system reports), on synthetic trace summaries and
  counter deltas, and nothing where the program has neither; and the
  merge reader on a small trace of the sharded search recorded on four
  v5e (``data/v5e4_sharded_tiny.xplane.pb.gz``, written by
  ``record_sharded_trace.py``);
- the new per-layer entries are declared for the new cell alone."""

import gzip
import json
import os
import types

import jax
import pytest
from jax.sharding import PartitionSpec as P

import numpy as np

from bench_suite import check, load, reference, run, spec, trace
from bench_suite.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e4_sharded_tiny.xplane.pb.gz")
CELL = "bigann32m-exact4.b2048"
NEW = ("shard_merge.ms", "shard_fixup.share")
SEED = 2 ** 31 + 11


@pytest.fixture(scope="module", autouse=True)
def small_blocks():
    """Row blocks of 256 rows, so that the tiny base has 4 per device."""
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(reference, "ROW_BLOCK", 256)
        yield


def _tiny_config() -> dict:
    with open(os.path.join(HERE, "data", "bigann32m-exact4.tiny.json")) as f:
        return json.load(f)


def _cell() -> spec.Cell:
    cell = tiny.shrink(spec.find_cell(CELL))
    cfg = _tiny_config()
    cell.config = {k: cfg[k] for k in ("name", "system", "data", "k",
                                       "check")}
    return cell


def _summary(kernels) -> trace.Summary:
    return trace.Summary(window_s=1.0, busy_s=0.5, kernels=kernels,
                         modules={}, spans={}, idle_gaps=[])


def test_tiny_config_is_the_cell_shrunk():
    cfg, full = _tiny_config(), spec.load_config("bigann32m-exact4")
    assert {k: v for k, v in cfg["data"].items() if k not in cfg["shrunk"]} \
        == {k: v for k, v in full["data"].items() if k not in cfg["shrunk"]}
    for key in ("system", "k", "check"):
        assert cfg[key] == full[key]
    assert full["data"]["placement"] == reference.ROW_SHARDED
    assert spec.find_cell(CELL).chips == 4


@pytest.mark.parametrize("traced", [False, True])
def test_tiny_cell_rehearsal(traced):
    cell = _cell()
    devices = jax.devices()[:cell.chips]
    m = run.measure(cell, SEED, 1.0, False, devices)
    assert m.base.sharding.spec == P("rows")
    assert m.stats_delta["cert_checks"] > 0
    # the window compiled nothing, so it traced no collective either
    assert m.stats_delta["collectives_traced"] == 0
    if traced:
        m.trace = _summary({"fused_l2_group_topk_packed": 0.2,
                            "all-gather-start": 0.01,
                            "all-gather-done": 0.02})
    out, ans, _ = run.report(m, devices, tiny.CPU_PEAKS)
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] > 0 and out["failed"] == 0 and len(ans.rows)
    assert ans.ids.shape[1] == 100
    entries = cell.per_layer if traced else cell.end_to_end
    assert set(out["metrics"]) == {e["name"] for e in entries}
    assert out["metrics"].get("shard_fixup.share", {"value": 0})["value"] >= 0
    assert all(v["value"] > 0 for k, v in out["metrics"].items()
               if k != "shard_fixup.share")


@pytest.mark.parametrize("fault", ["stale", "altered", "half", "wrong_ids"])
def test_broken_sharded_path_is_not_correct(fault):
    from bench_suite.tests import test_control

    class Factory(test_control._Broken):
        pass

    Factory.fault = fault
    cell = _cell()
    devices = jax.devices()[:cell.chips]
    m = run.measure(cell, SEED + 1, 1.0, False, devices,
                    system_factory=Factory)
    out, _, _ = run.report(m, devices, tiny.CPU_PEAKS)
    assert not out["correct"], out["checks"]
    if fault == "wrong_ids":
        # the distances are true: only an id-level number can catch it
        lim = cell.config["check"]["limits"]["dist_err"]
        assert out["checks"]["dist_err"]["value"] <= lim["max"]


class _Control:
    """The plain reference, its cross term in three bf16 passes, answering
    in the program's place."""

    def __init__(self, cfg, base, pool, max_rows):
        self.base, self.pool, self.k = base, pool, int(cfg["k"])

    def submit(self, start, rows):
        ids = (start + np.arange(rows)) % self.pool.shape[0]
        return check.reference_answers(self.base, self.pool, ids, self.k,
                                       "bf16x3")

    def wait(self, handle, timeout):
        return handle

    def stats(self):
        return {}

    def close(self):
        pass


@pytest.mark.parametrize("seed", [11, 2 ** 32 + 3, 987654321])
def test_control_in_the_programs_place_is_not_correct(seed):
    # 16k rows, as test_control's: neighbours as close, relative to the
    # norms, as at full size, so rounding reads as it does on the chip
    cell = _cell()
    cell.config["data"]["n_rows"] = 16384
    devices = jax.devices()[:cell.chips]
    m = run.measure(cell, seed, 1.0, False, devices,
                    system_factory=_Control)
    out, _, _ = run.report(m, devices, tiny.CPU_PEAKS)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert not out["correct"], out["checks"]


def _run(kernels=None, stats=None, requests=4):
    window = types.SimpleNamespace(requests=[
        load.Request(rid=i, start=0, rows=2048, t_due=0.0)
        for i in range(requests)])
    return types.SimpleNamespace(
        trace=None if kernels is None else _summary(kernels),
        window=window, stats_delta=stats or {})


def test_merge_reader():
    read = spec.metric_reader("shard_merge.ms")
    kernels = {"fused_l2_group_topk_packed": 1.0, "all-gather-start": 0.002,
               "all-gather-done": 0.001, "collective-permute": 0.0005,
               "collective-permute-done": 0.0005, "fusion": 0.3}
    assert read(_run(kernels)) == pytest.approx(1e3 * 0.004 / 4)
    # one chip, or a program without the merge: nothing
    assert read(_run({"fused_l2_group_topk_packed": 1.0})) is None
    assert read(_run()) is None


def test_merge_reader_on_a_recorded_four_chip_trace():
    """Three 2048-query searches over four v5e chips: the merge's
    collective-permute halves, as the chip's trace names them, are what
    the reader sums."""
    with open(RECORDED, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(
            gzip.decompress(f.read()))
    summary = trace.reduce_profile(data)
    assert len(summary.spans["bench.step"]) == 3
    assert len(summary.spans["distance.sharded_dispatch"]) == 3
    merge = {k: v for k, v in summary.kernels.items()
             if k.startswith(("all-gather", "collective-permute"))}
    assert set(merge) == {"collective-permute-start",
                          "collective-permute-done"}
    assert summary.kernels["fused_l2_group_topk_packed"] > 0
    run = _run(requests=3)
    run.trace = summary
    got = spec.metric_reader("shard_merge.ms")(run)
    assert got == pytest.approx(1e3 * sum(merge.values()) / 3)
    assert 0 < got < 1e3 * summary.window_s / 3


def test_fixup_reader():
    read = spec.metric_reader("shard_fixup.share")
    assert read(_run(stats={"cert_checks": 800, "cert_fixups": 12})) == \
        pytest.approx(0.015)
    assert read(_run(stats={"cert_checks": 800, "cert_fixups": 0})) == 0.0
    # a program that counts no certificates at this site
    assert read(_run(stats={})) is None
    assert read(_run(stats={"cert_checks": 0, "cert_fixups": 0})) is None


def test_new_entries_point_at_the_new_cell_only():
    bench = spec.load_benchmark()
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in NEW:
        assert declared[name]["workloads"] == [CELL]
        assert declared[name]["layer"] == "sharded exact KNN"
        assert declared[name]["moves"] == "qps"
    new = spec.find_cell(CELL)
    assert {m["name"] for m in new.end_to_end} == {"qps", "setup_s"}
    assert {m["name"] for m in new.per_layer} == {
        "device_idle.thru", "fused_topk_roofline", *NEW}
    for w in bench["workloads"]:
        if w["name"] != CELL:
            per_layer = {m["name"] for m in spec.find_cell(w["name"]).per_layer}
            assert not per_layer & set(NEW), w["name"]
