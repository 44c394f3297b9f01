"""Mean wait of a batch for co-riders, in ms: the ``serving.flush_wait``
spans of the engine's batcher summed and divided by the batches
(``serving.execute_batch`` spans) in the traced window. A batch whose
request the batcher dispatched at once has no such span and counts as
0. Nothing when the window has no ``serving.device_wait`` span: the
program then lacks the batcher's spans."""


def read(run):
    if run.trace is None:
        return None
    spans = run.trace.spans
    batches = len(spans.get("serving.execute_batch", ()))
    if not batches or "serving.device_wait" not in spans:
        return None
    return 1e3 * sum(spans.get("serving.flush_wait", ())) / batches
