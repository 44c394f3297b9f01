"""The trace reduction, on a synthetic trace with known intervals and on
a small trace recorded on a v5e (``data/v5e_tiny.xplane.pb``, written by
``record_trace.py``)."""

import os

import jax
import pytest

from bench_suite import trace

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "data", "v5e_tiny.xplane.pb")

# window 1000..11000 ns; device ops 1000..3000 (the fused kernel) and
# 6000..7000 (a fusion), and one op of a second device; a span
# 3000..5500 covers most of the first gap
SYNTHETIC = '''
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 5000000 duration_ps: 1000000 }
    events { metadata_id: 2 offset_ps: 5500000 duration_ps: 1000000 }
  }
  lines { id: 2 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 3 offset_ps: 0 duration_ps: 7000000 }
  }
  event_metadata { key: 1 value { id: 1
    name: "%fused_l2_group_topk_packed.1 = (f32[8]) custom-call()" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.3 = f32[8] fusion()" } }
  event_metadata { key: 3 value { id: 3 name: "jit__knn_fused_core(123)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "main/1" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 10000000 }
    events { metadata_id: 2 offset_ps: 2000000 duration_ps: 2500000 }
    events { metadata_id: 3 offset_ps: 2000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "bench.window" } }
  event_metadata { key: 2 value { id: 2 name: "ann.search_ivf_flat" } }
  event_metadata { key: 3 value { id: 3 name: "ReadSyncFlag" } }
}
'''


def test_synthetic_intervals():
    s = trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(
        SYNTHETIC))
    assert s.window_s == pytest.approx(10e-6)
    # union of [1000,3000] and [6000,7500]: overlapping ops count once
    assert s.busy_s == pytest.approx(3.5e-6)
    assert s.idle_share == pytest.approx(0.65)
    assert s.kernels == pytest.approx({"fused_l2_group_topk_packed": 2e-6,
                                       "fusion": 2e-6})
    assert s.modules == pytest.approx({"jit__knn_fused_core": 7e-6})
    # runtime events are not named spans; the window span is not a layer
    assert list(s.spans) == ["ann.search_ivf_flat"]
    assert s.spans["ann.search_ivf_flat"] == pytest.approx([2.5e-6])
    assert dict(s.idle_gaps) == pytest.approx(
        {"ann.search_ivf_flat": 3e-6, trace.NO_SPAN: 3.5e-6})


def test_no_window_span_is_an_error():
    text = SYNTHETIC.replace('name: "bench.window"', 'name: "other.span"')
    with pytest.raises(ValueError):
        trace.reduce_profile(jax.profiler.ProfileData.from_text_proto(text))


def test_op_names():
    assert trace.op_name("%cond.47 = (f32[2048,64]) conditional()") == "cond"
    assert trace.op_name("%fine_scan_list_major.2 = x") == "fine_scan_list_major"


@pytest.mark.skipif(not os.path.exists(RECORDED), reason="not recorded")
def test_recorded_v5e_trace():
    s = trace.reduce(RECORDED)
    assert 0 < s.busy_s < s.window_s
    assert 0 < s.idle_share < 1
    assert s.kernels and s.modules
    assert "bench.step" in s.spans
    assert sum(v for _, v in s.idle_gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-6)
