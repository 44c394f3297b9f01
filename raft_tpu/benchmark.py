"""Micro-benchmark harness.

(ref: cpp/bench/prims/common/benchmark.hpp:59,99 — the google-benchmark
``fixture`` with RMM pool option and ``cuda_event_timer`` for device-time
measurement, plus data generators like ``BlobsFixture:176``. The TPU
equivalent times with the host clock around work that ends in
``jax.block_until_ready``.)
"""

from __future__ import annotations

import time
from typing import Callable, Dict, Optional

import jax

from raft_tpu.core.resources import ensure_resources


class Fixture:
    """(ref: bench/prims/common/benchmark.hpp ``class fixture``)"""

    def __init__(self, res=None, reps: int = 5, warmup: int = 1):
        self.res = ensure_resources(res)
        self.reps = reps
        self.warmup = warmup

    def run(self, fn: Callable, *args, name: Optional[str] = None,
            model: Optional[Dict] = None) -> Dict[str, float]:
        """Time fn(*args); returns {"seconds", ...}.
        (ref: ``cuda_event_timer`` role)

        ``model`` (optional) is an analytic-prediction dict (e.g.
        ``costmodel.fused_traffic_model``) merged into the result under
        ``model_*`` keys — the predicted half of every
        predicted-vs-measured comparison rides the same artifact as the
        measured half.

        The result is also emitted through the observability registry
        (``raft_tpu_benchmark_seconds{bench=<name>}`` + a ``benchmark``
        event, keyed by ``name`` or the function's ``__name__``) so
        BENCH_*.json trajectories and ad-hoc measurements flow from one
        code path — see ``observability.bench_results()``.

        When tracing is enabled the result ALSO carries the static cost
        model: ``flops``, ``bytes_accessed``, ``arithmetic_intensity``,
        ``peak_hbm_bytes``, ``bound`` (compute-/memory-bound at the
        chip's ridge) and ``roofline_frac`` (roofline-perfect time /
        measured time) — captured once per (name, shape signature) via
        ``res.profiler`` (one analysis lowering, memoized). A callable
        the cost model cannot lower (host-side control flow) simply
        omits the fields.

        The first ``warmup`` calls compile and are not timed. Then all
        ``reps`` dispatches are timed in ONE host-clock span that ends
        in ``jax.block_until_ready`` (a device queues executions in
        dispatch order); two spans are timed and the MIN taken, so a
        transient host stall (GC) in one cannot inflate the result."""
        for _ in range(max(1, self.warmup)):
            jax.block_until_ready(fn(*args))
        spans = []
        for _ in range(2):
            t0 = time.perf_counter()
            for _ in range(self.reps):
                out = fn(*args)
            jax.block_until_ready(out)
            spans.append(time.perf_counter() - t0)
        result = {"seconds": min(spans) / self.reps}
        bench_name = name or getattr(fn, "__name__", repr(fn))
        result.update(self._cost_fields(bench_name, fn, args,
                                        result["seconds"]))
        # resilience provenance: a nonzero degradation counter means
        # some hot path ran a ladder fallback this process — stamp it
        # so bench_report --check can refuse to gate (or baseline)
        # degraded evidence. Omitted when zero, keeping clean artifacts
        # byte-identical to the pre-resilience schema.
        try:
            from raft_tpu.resilience import degradation_count

            dc = degradation_count()
            if dc:
                result["resilience_degradations"] = dc
        except Exception:
            pass
        if model:
            result.update({
                (k if str(k).startswith("model_") else f"model_{k}"): v
                for k, v in model.items()})
        # drift ledger: the cost model's prediction vs THIS measurement,
        # per site. predicted_seconds is the roofline-perfect time the
        # model says this executable needs (roofline_frac · measured);
        # ``measured`` is True only on real TPU hardware — CPU-suite
        # entries are model-shape evidence and are never drift-gated
        # (tools/bench_report.py --check gates the measured ones).
        try:
            from raft_tpu.observability.timeline import record_drift

            rf = result.get("roofline_frac")
            if isinstance(rf, (int, float)) and rf > 0:
                record_drift(
                    bench_name,
                    predicted_seconds=rf * result["seconds"],
                    predicted_bytes=result.get(
                        "model_total_bytes", result.get("bytes_accessed")),
                    measured_seconds=result["seconds"],
                    measured_bytes=result.get("bytes_accessed"),
                    measured=jax.default_backend() == "tpu",
                    platform=jax.default_backend())
        except Exception:
            pass
        # quality telemetry (ISSUE 10): drain the pending certificate
        # stats (the measured program has completed — the device
        # scalars resolve for free) and stamp the cumulative quality
        # block, so fixup-rate evidence rides every BENCH artifact in
        # the already-gated schema (bench_report --check [quality]).
        # Omitted when the process recorded none, keeping quality-free
        # artifacts byte-identical to the previous schema.
        try:
            from raft_tpu.observability.quality import quality_block

            qb = quality_block()
            if qb:
                result["quality"] = qb
        except Exception:
            pass
        from raft_tpu.observability import record_benchmark

        record_benchmark(bench_name, result)
        return result

    def _cost_fields(self, name: str, fn: Callable, args,
                     seconds: float) -> Dict[str, float]:
        """Static-cost + roofline fields for one measured callable (see
        run()); {} when tracing is disabled or the fn resists analysis.
        Runs AFTER timing, so the analysis compile never pollutes the
        measurement."""
        from raft_tpu import observability as obs
        from raft_tpu.observability import costmodel

        if not obs.tracing_enabled():
            return {}
        profiler = self.res.profiler
        rec = profiler.capture_fn(name, fn, *args)
        if rec is None:
            return {}
        est = costmodel.roofline(rec, profiler.spec, seconds=seconds)
        out = {"flops": rec.flops, "bytes_accessed": rec.bytes_accessed,
               "arithmetic_intensity": rec.arithmetic_intensity,
               "peak_hbm_bytes": rec.peak_hbm_bytes, "bound": est.bound}
        if est.utilization is not None:
            out["roofline_frac"] = est.utilization
        return out

    def throughput(self, fn: Callable, nbytes: float, *args,
                   name: Optional[str] = None) -> Dict[str, float]:
        r = self.run(fn, *args, name=name)
        r["gb_per_s"] = nbytes / r["seconds"] / 1e9
        return r


class BlobsFixture(Fixture):
    """(ref: benchmark.hpp ``BlobsFixture:176``)"""

    def __init__(self, n_samples: int, n_features: int, n_clusters: int = 8,
                 seed: int = 0, **kw):
        super().__init__(**kw)
        from raft_tpu.random import RngState, make_blobs

        self.X, self.labels = make_blobs(
            self.res, RngState(seed), n_samples, n_features,
            n_clusters=n_clusters)
        jax.block_until_ready(self.X)
