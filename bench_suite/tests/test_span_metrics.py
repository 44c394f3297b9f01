"""The span readers of the served IVF path on synthetic trace summaries:
sums per search (or per batch) from known durations, and nothing when
the program that ran has no such spans."""

import types

import pytest

from bench_suite import spec, trace

READERS = ("serving_wait.ms", "serving_sync.ms", "ivf_probe.ms",
           "ivf_plan.ms", "ivf_scan.ms", "ivf_chunks.per_search")


def _run(spans):
    summary = trace.Summary(window_s=1.0, busy_s=0.1, kernels={},
                            modules={}, spans=spans, idle_gaps=[])
    return types.SimpleNamespace(trace=summary)


def _read(name, spans):
    return spec.metric_reader(name)(_run(spans))


#: two searches of four chunks, each chunk planned and synced; one
#: rerun; two batches, one of which waited for co-riders
SPANS = {
    "serving.execute_batch": [0.040, 0.036],
    "serving.device_wait": [0.002, 0.001],
    "serving.flush_wait": [0.003],
    "ann.search_ivf_flat": [0.035, 0.033],
    "ann.coarse_probe": [0.001, 0.002],
    "ann.probe_fetch": [0.004, 0.003],
    "ann.fine_scan_plan": [0.0005] * 10,
    "ann.fine_scan": [0.001] * 8,
    "ann.certificate_sync": [0.002] * 8,
    "ann.fine_scan_rerun": [0.004],
}

EXPECTED = {
    "serving_wait.ms": 1e3 * 0.003 / 2,
    "serving_sync.ms": 1e3 * 0.003 / 2,
    "ivf_probe.ms": 1e3 * 0.010 / 2,
    "ivf_plan.ms": 1e3 * 0.005 / 2,
    "ivf_scan.ms": 1e3 * (0.008 + 0.016 + 0.004) / 2,
    "ivf_chunks.per_search": 4.0,
}


@pytest.mark.parametrize("name", READERS)
def test_reader_sums(name):
    assert _read(name, SPANS) == pytest.approx(EXPECTED[name])


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_its_spans(name):
    """The parent program's trace: the harness's and the search's own
    spans, none of the new ones; and a run with no trace."""
    parent = {k: SPANS[k] for k in ("serving.execute_batch",
                                    "ann.search_ivf_flat",
                                    "distance.knn")
              if k in SPANS}
    assert _read(name, parent) is None
    assert _read(name, {}) is None
    assert spec.metric_reader(name)(types.SimpleNamespace(trace=None)) \
        is None


def test_batches_that_never_waited_read_zero():
    spans = {k: v for k, v in SPANS.items() if k != "serving.flush_wait"}
    assert _read("serving_wait.ms", spans) == 0.0


def test_readers_are_declared_for_the_served_cell():
    cell = spec.find_cell("sift1m-ivf.single")
    declared = {m["name"]: m for m in cell.per_layer}
    for name in READERS:
        assert declared[name]["source"] == "program_span"
        assert declared[name]["moves"] == "p50_ms"
    exact = spec.find_cell("sift1m-exact.b2048")
    assert not set(READERS) & {m["name"] for m in exact.per_layer}
