"""Mean duration, in ms, of the ``ann.search_ivf_flat`` host spans (the
repo's ``@instrument``) inside the traced window."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.spans.get("ann.search_ivf_flat")
    return 1e3 * sum(d) / len(d) if d else None
