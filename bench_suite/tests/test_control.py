"""The check fails what it must. At a tiny size on the CPU:

- the control, the plain reference put in the program's place with its
  cross term in three bf16 passes, is not correct;
- a run whose timed path is broken underneath (answers of an earlier
  request returned again, one id of each answer altered where it is
  produced, half of each batch's rows answered with the other half's
  answers, wrong ids, the top-k of half the base, reported with their
  true distances) comes out not correct, through the harness's own
  measure and report.

The chip readings these limits were set from are in PERF.md."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench_suite import check, reference, run, spec
from bench_suite.tests import tiny
from bench_suite.tools import calibrate

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEEDS = [11, 2 ** 32 + 3, 987654321]


class _Broken:
    """The configuration's system, broken underneath."""

    fault = None

    def __init__(self, cfg, base, pool, max_rows):
        self.inner = spec.system_module(cfg["system"]).System(
            cfg, base, pool, max_rows)
        self.n_rows = int(base.shape[0])
        self.base, self.pool, self.k = base, pool, int(cfg["k"])
        self.last = None
        self.calls = 0

    def submit(self, start, rows):
        return self.inner.submit(start, rows), start, rows

    def wait(self, handle, timeout):
        h, start, rows = handle
        d, i = (np.array(a) for a in self.inner.wait(h, timeout))
        self.calls += 1
        if self.fault == "stale":
            prev, self.last = self.last, (d, i)
            if prev is not None:
                d, i = np.resize(prev[0], d.shape), np.resize(prev[1],
                                                              i.shape)
        elif self.fault == "altered":
            i[0, -1] = (i[0, -1] + 1) % self.n_rows
        elif self.fault == "half" and rows > 1:
            h2 = rows // 2
            d[h2:2 * h2], i[h2:2 * h2] = d[:h2], i[:h2]
        elif self.fault == "wrong_ids":
            q = jnp.take(self.pool, jnp.asarray(
                (start + np.arange(rows)) % self.pool.shape[0]), axis=0)
            _, i = reference.exact_topk(q, self.base[:self.n_rows // 2],
                                        self.k)
            d = reference.true_distances(q, self.base, i)
        return d, i

    def stats(self):
        return self.inner.stats()

    def close(self):
        self.inner.close()


def _measure(cell, seed, factory=None):
    devices = jax.devices()[:1]
    return run.measure(cell, seed, 1.0, False, devices,
                       system_factory=factory)


@pytest.mark.parametrize("name", CELLS)
def test_control_is_not_correct(name):
    # 16k rows: neighbours as close, relative to the norms, as at full
    # size, so rounding reads as it does on the chip
    cell = tiny.shrink(spec.find_cell(name), n_rows=16384)
    limits = cell.config["check"]["limits"]
    for seed in SEEDS:
        m = _measure(cell, seed)
        out, ans, ref_ids = run.report(m, jax.devices()[:1], tiny.CPU_PEAKS)
        assert out["correct"]
        ctrl = calibrate.control_numbers(m, ans, ref_ids, list(limits))
        assert not check.judge(ctrl, limits), ctrl


@pytest.mark.parametrize("fault", ["stale", "altered", "half", "wrong_ids"])
@pytest.mark.parametrize("name", CELLS)
def test_broken_path_is_not_correct(name, fault):
    cell = tiny.shrink(spec.find_cell(name))
    if fault == "half" and cell.traffic["rows"]["max"] < 2:
        pytest.skip("1-row requests have no half to leave out")

    class Factory(_Broken):
        pass

    Factory.fault = fault
    m = _measure(cell, SEEDS[1], Factory)
    out, _, _ = run.report(m, jax.devices()[:1], tiny.CPU_PEAKS)
    assert not out["correct"], out["checks"]
    if fault == "wrong_ids":
        # the distances are true: only an id-level number can catch it
        lim = cell.config["check"]["limits"]["dist_err"]
        assert out["checks"]["dist_err"]["value"] <= lim["max"]
