"""The fused L2 top-k kernel's share of its roofline, in %.

The least time is the benchmark's own count of the work exact k-NN
needs, the same whatever implements it, over the chip's peaks, summed
over the calls (requests) answered while the trace ran:

- operations of a call of ``n_q`` queries: ``2 * n_q * n * d``;
- bytes: every base element once at 1 byte (the narrowest width any
  path of the repo streams), plus the queries (f32) and the answers
  (f32 distance and int32 id per neighbour);
- on ``c`` chips the base is split over them: each chip does
  ``1 / c`` of the operations and streams ``1 / c`` of the base, and
  still reads every query and writes every answer;
- least time of a call = max(operations / the int8 peak,
  bytes / the HBM peak), per chip.

The highest peak and the narrowest width are used so that no later
implementation (int8 MXU passes, fewer passes) can read over 100%. The
time is the device time of the kernel's events (op names
``fused_l2_*topk*``) in the trace, which the reduction averages over the
devices.
"""


def work(n_q: int, n: int, d: int, k: int, chips: int = 1):
    """(operations, bytes) on each of ``chips`` chips of exact k-NN of
    ``n_q`` queries over a base split evenly across them."""
    ops = 2.0 * n_q * n * d / chips
    nbytes = 1.0 * n * d / chips + 4.0 * n_q * d + 8.0 * n_q * k
    return ops, nbytes


def least_time(ops: float, nbytes: float, peaks: dict) -> float:
    return max(ops / peaks["int8_ops_per_s"],
               nbytes / peaks["hbm_bytes_per_s"])


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.kernels.items()
            if k.startswith("fused_l2_") and "topk" in k)
    if t <= 0:
        return None
    data = run.config["data"]
    n, d, k = int(data["n_rows"]), int(data["dim"]), int(run.config["k"])
    least = sum(least_time(*work(r.rows, n, d, k, run.chips), run.peaks)
                for r in run.window.requests if r.error is None)
    return 100.0 * least / t
