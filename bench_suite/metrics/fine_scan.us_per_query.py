"""Device time of the IVF fine scan per query row answered while the trace
ran, in us: the list-major Pallas kernel's events (op names
``fine_scan*``) plus the query-major gather's programs (XLA modules
``jit__fine_scan*``), whichever the plan picked."""


def read(run):
    if run.trace is None:
        return None
    t = (sum(v for k, v in run.trace.kernels.items()
             if k.startswith("fine_scan"))
         + sum(v for k, v in run.trace.modules.items()
               if k.startswith("jit__fine_scan")))
    rows = run.rows_traced()
    if t <= 0 or rows == 0:
        return None
    return 1e6 * t / rows
