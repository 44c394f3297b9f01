"""Fine scan per IVF search, in ms: the ``ann.fine_scan`` (each chunk's
dispatch), ``ann.certificate_sync`` (the wait to learn whether to
rerun) and ``ann.fine_scan_rerun`` spans summed over the traced window
and divided by its ``ann.search_ivf_flat`` spans."""

PARTS = ("ann.fine_scan", "ann.certificate_sync", "ann.fine_scan_rerun")


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans
    searches = len(spans.get("ann.search_ivf_flat", ()))
    if not searches or "ann.fine_scan" not in spans:
        return None
    return 1e3 * sum(sum(spans.get(p, ())) for p in PARTS) / searches
