"""Mean duration, in ms, of the ``serving.device_wait`` spans: the
engine's wait for the device and its copy of the answers to the host,
once per batch."""


def read(run):
    if run.trace is None:
        return None
    d = run.trace.spans.get("serving.device_wait")
    return 1e3 * sum(d) / len(d) if d else None
