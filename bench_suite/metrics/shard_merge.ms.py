"""Device time of the cross-shard merge per answered batch, in ms: the
collective op events (``all-gather*``, ``collective-permute*``, their
``-start``/``-done`` halves included) in the trace, which the reduction
averages over the devices, over the requests answered while the trace
ran. The sharded search's merge is its only cross-chip traffic. Nothing
where no collective ran (one chip, or a program without the merge)."""

COLLECTIVES = ("all-gather", "collective-permute")


def read(run):
    if run.trace is None:
        return None
    t = sum(v for k, v in run.trace.kernels.items()
            if k.startswith(COLLECTIVES))
    batches = sum(1 for r in run.window.requests if r.error is None)
    if t <= 0 or not batches:
        return None
    return 1e3 * t / batches
