"""Coarse probe per IVF search, in ms: the ``ann.coarse_probe`` and
``ann.probe_fetch`` spans (the dispatch, and the wait for the probe
table and its copy to the host) summed over the traced window and
divided by its ``ann.search_ivf_flat`` spans."""

PARTS = ("ann.coarse_probe", "ann.probe_fetch")


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans
    searches = len(spans.get("ann.search_ivf_flat", ()))
    if not searches or not any(p in spans for p in PARTS):
        return None
    return 1e3 * sum(sum(spans.get(p, ())) for p in PARTS) / searches
