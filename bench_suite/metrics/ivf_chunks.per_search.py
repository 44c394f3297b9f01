"""Fine-scan chunks per IVF search: the ``ann.fine_scan`` spans (one per
chunk's dispatch, whichever schedule runs) over the
``ann.search_ivf_flat`` spans in the traced window."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans
    searches = len(spans.get("ann.search_ivf_flat", ()))
    scans = spans.get("ann.fine_scan")
    return len(scans) / searches if searches and scans else None
