"""Run one cell of the benchmark and print its result line.

    python3 bench_suite/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, one run:

1. the cell's data, made from ``--seed`` on the device: the whole base
   on one device, or, where the configuration's ``data`` says
   ``"placement": "row_sharded"``, its rows split over the cell's chips,
   each chip drawing its own (``reference.make_data``);
2. the system built and warmed by the cell's own traffic (``setup_s``);
3. the window: the traffic offered for ``--seconds``, the profiler on
   with ``--trace 1``;
4. once the window has closed and the system is freed, its answers
   compared with the plain reference (``correct``);
5. the last line of stdout: one JSON object with ``correct``,
   ``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
   with ``--trace 1``), the numbers compared beside their limits last.

It runs only on a TPU whose ``device_kind`` is in ``peaks.json``: any
other platform, or fewer chips than the cell asks for, exits non-zero
with no result. It sets no ``RAFT_TPU_*`` knob: every cell measures the
plan and defaults a deployment gets.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Optional  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from bench_suite import check, load, spec  # noqa: E402
from bench_suite import trace as trace_mod  # noqa: E402

#: programs compiled (or loaded from the persistent cache) by XLA
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class NoChip(RuntimeError):
    """The machine is not one the cell can be measured on."""


@dataclasses.dataclass
class Run:
    """What the metric readers read."""
    config: dict
    peaks: dict
    setup_s: float
    window: load.Window
    stats_delta: dict
    check_values: dict
    trace: Optional[trace_mod.Summary]
    chips: int              # devices the cell ran on

    def rows_traced(self) -> int:
        """Query rows answered while the trace ran: every request of the
        window, the drain after its close included (the ``bench.window``
        span ends when the last one is answered)."""
        return sum(r.rows for r in self.window.requests if r.error is None)


def load_peaks(kind: str) -> dict:
    with open(os.path.join(spec.SUITE, "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise NoChip(f"device kind {kind!r} is not in peaks.json")
    return table["devices"][kind]


def device_check(chips: int):
    """(devices, peaks) of the first ``chips`` devices; NoChip unless
    they are TPUs of a kind in the peak table."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX found {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return devs[:chips], load_peaks(devs[0].device_kind)


class CompileCounter:
    """Counts XLA compilations while armed (a ``jax.monitoring``
    listener of the harness's own)."""

    def __init__(self):
        import jax

        self.armed = False
        self.names: list = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "?",
            **_) -> None:
        if self.armed and event == COMPILE_EVENT:
            self.names.append(fun_name)


@dataclasses.dataclass
class Measured:
    """A run up to the close of its window, the system freed."""
    cell: spec.Cell
    seed: int
    base: object            # the data, on the cell's device(s)
    pool: object
    setup_s: float
    window: load.Window
    stats_delta: dict
    memory_peak_bytes: int
    compiles: list          # programs compiled inside the window
    trace: Optional[trace_mod.Summary]


def measure(cell: spec.Cell, seed: int, seconds: float, traced: bool,
            devices, t_start: float = T_START,
            system_factory=None) -> Measured:
    """Data, set-up, warm-up and the window of one run.
    ``system_factory`` stands in for the configuration's system (the
    tests use it to break the timed path)."""
    import jax

    from bench_suite import reference

    cfg, traffic = cell.config, cell.traffic
    counter = CompileCounter()
    t0 = time.perf_counter()
    base, pool = reference.make_data(seed, cfg["data"], devices)
    jax.block_until_ready((base, pool))
    t1 = time.perf_counter()
    factory = (system_factory
               or spec.system_module(cfg["system"], cell.root).System)
    system = factory(cfg, base, pool, int(traffic["rows"]["max"]))
    n_pool = int(pool.shape[0])
    t2 = time.perf_counter()
    load.drive(system, traffic, load.Plan(traffic, n_pool, seed, stream=0),
               float(traffic["warm_s"]), root=cell.root)
    split = {"start": t0 - t_start, "data": t1 - t0,
             **getattr(system, "setup_split", {"system": t2 - t1}),
             "warm_traffic": time.perf_counter() - t2}
    stats0 = system.stats()
    keeper = load.Keeper(cfg["check"].get("keep_requests"), seed)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if traced else None
    box = {}

    def open_window():
        box["setup_s"] = time.perf_counter() - t_start
        if traced:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.enable_hlo_proto = False
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            box["span"] = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            box["span"].__enter__()
        counter.armed = True

    window = load.drive(system, traffic, load.Plan(traffic, n_pool, seed),
                        seconds, keeper, on_open=open_window, root=cell.root)
    counter.armed = False
    after = {}
    t3 = time.perf_counter()
    if traced:
        box["span"].__exit__(None, None, None)
        jax.profiler.stop_trace()
        after["trace_stop"] = time.perf_counter() - t3
    stats1 = system.stats()
    stats_delta = {k: stats1[k] - stats0.get(k, 0) for k in stats1
                   if isinstance(stats1[k], (int, float))}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    system.close()
    del system
    gc.collect()
    summary = None
    if traced:
        t4 = time.perf_counter()
        summary = trace_mod.reduce(trace_dir)
        shutil.rmtree(trace_dir, ignore_errors=True)
        after["trace_reduce"] = time.perf_counter() - t4
    after["after_window"] = time.perf_counter() - t3
    print("bench: set-up by step, s: "
          + ", ".join(f"{k} {v:.3f}" for k, v in split.items()), flush=True)
    print("bench: after the window, s: "
          + ", ".join(f"{k} {v:.3f}" for k, v in after.items()), flush=True)
    return Measured(cell=cell, seed=seed, base=base, pool=pool,
                    setup_s=box["setup_s"], window=window,
                    stats_delta=stats_delta, memory_peak_bytes=int(peak),
                    compiles=counter.names, trace=summary)


def report(m: Measured, devices, peaks: dict):
    """Check the answers against the reference, read the metrics, and
    build the result object. Returns (result, checked answers, the
    reference's ids for them)."""
    cfg, window = m.cell.config, m.window
    t0 = time.perf_counter()
    values, limits, correct, ans, ref_ids = check.evaluate(
        cfg, m.base, m.pool, window.requests)
    check_s = time.perf_counter() - t0
    run = Run(config=cfg, peaks=peaks,
              setup_s=m.setup_s, window=window, stats_delta=m.stats_delta,
              check_values=values, trace=m.trace, chips=len(devices))
    entries = m.cell.per_layer if m.trace is not None else m.cell.end_to_end
    metrics = spec.read_metrics(entries, run, m.cell.root)
    failed = sum(r.error is not None for r in window.requests)
    print(f"bench: compilations inside the window: {len(m.compiles)} "
          f"{sorted(set(m.compiles))[:20]}", flush=True)
    late = sorted(window.lateness_s)
    if late:
        print(f"bench: generator lateness over {len(late)} requests: "
              f"p50 {1e3 * late[len(late) // 2]:.3f} ms, "
              f"max {1e3 * late[-1]:.3f} ms", flush=True)
    print(f"bench: engine counters over the window: {m.stats_delta}",
          flush=True)
    print(f"bench: {len(ans.rows)} answered rows checked in "
          f"{check_s:.3f} s", flush=True)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": m.memory_peak_bytes}
    out = {"correct": bool(correct), "attempted": len(window.requests),
           "failed": int(failed), "metrics": metrics, "device": device}
    if m.trace is not None:
        device["busy_s"] = m.trace.busy_s
        device["window_s"] = m.trace.window_s
        out["breakdown"] = {
            "device_ops": [[k, v] for k, v in m.trace.top_ops(10)],
            "idle_gaps": [[k, v] for k, v in m.trace.idle_gaps[:10]]}
    out["checks"] = {name: {"value": values.get(name),
                            "limit": check.limit_of(lim)}
                     for name, lim in limits.items()}
    return out, ans, ref_ids


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = spec.find_cell(args.workload)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from raft_tpu.utils.compile_cache import use_compile_cache

    use_compile_cache()
    # every program goes to the cache, so each run after the first
    # loads what it would otherwise compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        devices, peaks = device_check(cell.chips)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 3
    m = measure(cell, args.seed, args.seconds, bool(args.trace), devices)
    out, _, _ = report(m, devices, peaks)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
