"""The CPU rehearsal: every cell of BENCHMARK.json driven end to end at
a tiny size (the same traffic generator, systems, check and metric
readers as a chip run), the per-layer readers on a synthetic trace, and
a new cell, arrival process and per-layer metric added as files alone."""

import json
import os
import shutil

import jax
import pytest

from bench_suite import run, spec, trace
from bench_suite.tests import test_trace, tiny

CELLS = [w["name"] for w in spec.load_benchmark()["workloads"]]
SEED = 2 ** 33 + 17


def _run(cell, traced_summary=None):
    devices = jax.devices()[:1]
    m = run.measure(cell, SEED, 1.0, False, devices)
    m.trace = traced_summary
    return run.report(m, devices, tiny.CPU_PEAKS)


@pytest.fixture(scope="module")
def synthetic():
    return trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(
        test_trace.SYNTHETIC))


def test_harness_refuses_the_cpu():
    with pytest.raises(run.NoChip):
        run.device_check(1)


@pytest.mark.parametrize("name", CELLS)
def test_cell_end_to_end(name):
    cell = tiny.shrink(spec.find_cell(name))
    out, ans, _ = _run(cell)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert len(ans.rows) > 0
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(cell.config["check"]["limits"])


@pytest.mark.parametrize("name", CELLS)
def test_per_layer_readers(name, synthetic):
    """Each per-layer reader of the cell runs on a trace summary and
    returns a number or nothing."""
    cell = tiny.shrink(spec.find_cell(name))
    out, _, _ = _run(cell, synthetic)
    assert set(out["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "device_idle.thru" in out["metrics"] or \
        "device_idle.lat" in out["metrics"]
    assert out["device"]["busy_s"] > 0
    assert out["breakdown"]["device_ops"]


#: an arrival process the harness has never seen: ``burst`` requests due
#: together every ``period_s``
BURST_LOOP = """
import time


def run(session):
    due = session.t0
    while due < session.t_end:
        reqs = [session.plan.next()
                for _ in range(int(session.traffic["burst"]))]
        sent = []
        for req in reqs:
            req.t_due = due
            sent.append((req, session.submit(req)))
        for req, handle in sent:
            session.finish(req, handle)
        due += float(session.traffic["period_s"])
        time.sleep(max(0.0, due - time.perf_counter()))
"""


def test_new_cell_and_metric_as_files_only(tmp_path, synthetic):
    root = str(tmp_path)
    shutil.copytree(spec.SUITE, os.path.join(root, "bench_suite"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = spec.load_benchmark()
    bench["workloads"].append({
        "name": "sift1m-exact.dummy", "config": "sift1m-exact",
        "traffic": "dummy", "chips": 1, "why": "rehearsal"})
    bench["per_layer"].append({
        "name": "dummy_rows", "unit": "rows", "better": "higher",
        "source": "program_counter", "layer": "device", "moves": "qps",
        "workloads": ["sift1m-exact.dummy"]})
    bench["end_to_end"][0]["workloads"].append("sift1m-exact.dummy")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    with open(os.path.join(root, "bench_suite", "traffic", "dummy.json"),
              "w") as f:
        json.dump({"loop": "burst", "burst": 4, "period_s": 0.05,
                   "rows": {"min": 3, "max": 5}, "warm_s": 0.2}, f)
    with open(os.path.join(root, "bench_suite", "loops", "burst.py"),
              "w") as f:
        f.write(BURST_LOOP)
    with open(os.path.join(root, "bench_suite", "metrics", "dummy_rows.py"),
              "w") as f:
        f.write("def read(run):\n    return run.rows_traced()\n")
    cell = tiny.shrink(spec.find_cell("sift1m-exact.dummy", root=root))
    assert cell.traffic["loop"] == "burst"
    out, _, _ = _run(cell, synthetic)
    assert out["correct"]
    # bursts of 4 every 50 ms over the 1-s window
    assert out["attempted"] >= 4 and out["attempted"] % 4 == 0
    assert out["metrics"]["dummy_rows"]["value"] > 0
    assert out["metrics"]["dummy_rows"]["unit"] == "rows"
