"""Host planning of the fine scan per IVF search, in ms: the
``ann.fine_scan_plan`` spans (schedule resolution, and per chunk the
list schedule and its upload) summed over the traced window and
divided by its ``ann.search_ivf_flat`` spans."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans
    searches = len(spans.get("ann.search_ivf_flat", ()))
    plan = spans.get("ann.fine_scan_plan")
    return 1e3 * sum(plan) / searches if searches and plan else None
