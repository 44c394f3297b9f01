"""Span tracing layered on the :mod:`raft_tpu.core.nvtx` range stack.

A *span* is an nvtx range that also reports into the metrics registry:
call count, dispatch wall time, bytes in/out — attributed to the
innermost ENCLOSING range at entry, exactly the way
``core.memory.ResourceMonitor`` attributes its memory samples. The span
itself is pushed as an nvtx range, so nested instrumented primitives
attribute to their caller's span (``distance.knn`` shows up as the
``range`` label of the ``matrix.select_k`` spans it triggers).

Timing semantics — *dispatch* vs *execute*: on an async runtime a
Python-side timer brackets trace+dispatch, not device execution (and
under ``jit`` tracing it runs once, at trace time). Span timings are
therefore exported as ``raft_tpu_span_seconds`` (dispatch wall time,
honest for eager callers, trace-time for jitted ones) while *execute*
time flows through :meth:`raft_tpu.benchmark.Fixture.run`, which forces
completion and subtracts the transport RTT via its probe, and emits
``raft_tpu_benchmark_seconds`` through the same registry. A span that
closes while an explain capture is active on its thread also adds its
time to the capture's ``stages`` under its name.

Disabled contract (``RAFT_TPU_DISABLE_TRACING``): ``instrument`` applied
in a disabled process returns the function UNCHANGED — zero overhead, no
wrapper frame. A runtime :func:`raft_tpu.observability.disable` leaves
the wrapper in place but short-circuits after one boolean attribute
check.
"""

from __future__ import annotations

import contextlib
import functools
import time
from typing import Callable, Iterator, Optional

import jax
import numpy as np

from raft_tpu.core import nvtx
from raft_tpu.observability import explain
from raft_tpu.observability.metrics import ENV_DISABLED, get_registry
from raft_tpu.observability.timeline import emit_span

SPAN_CALLS = "raft_tpu_span_calls_total"
SPAN_ERRORS = "raft_tpu_span_errors_total"
SPAN_SECONDS = "raft_tpu_span_seconds"
SPAN_BYTES_IN = "raft_tpu_span_bytes_in_total"
SPAN_BYTES_OUT = "raft_tpu_span_bytes_out_total"


def tree_nbytes(tree) -> int:
    """Total array payload bytes in a pytree. Non-array leaves (handles,
    scalars, strings) contribute 0; tracers report their aval size, so
    byte accounting stays correct under jit tracing."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        n = getattr(leaf, "nbytes", None)
        if isinstance(n, (int, np.integer)):
            total += int(n)
    return total


def _record(name: str, parent: str, seconds: float, bytes_in: int,
            bytes_out: int, error: bool) -> None:
    emit_span(name, parent, seconds, bytes_in, bytes_out, error,
              stack=nvtx.range_stack())
    # spans are the explain plane's stage timer: a capture active on
    # this thread sums each span's time under its name
    cap = explain.active()
    if cap is not None:
        cap.stages[name] = cap.stages.get(name, 0.0) + seconds
    reg = get_registry()
    labels = {"span": name, "range": parent}
    reg.counter(SPAN_CALLS, labels,
                help="Instrumented-span invocations").inc()
    if error:
        reg.counter(SPAN_ERRORS, labels,
                    help="Spans that exited with an exception").inc()
    reg.histogram(SPAN_SECONDS, labels,
                  help="Span dispatch wall time (seconds; trace-time "
                       "under jit)").observe(seconds)
    if bytes_in:
        reg.counter(SPAN_BYTES_IN, labels,
                    help="Array bytes entering the span").inc(bytes_in)
    if bytes_out:
        reg.counter(SPAN_BYTES_OUT, labels,
                    help="Array bytes produced by the span").inc(bytes_out)
    reg.emit({"type": "span", "span": name, "range": parent,
              "seconds": seconds, "bytes_in": bytes_in,
              "bytes_out": bytes_out, "error": error})


@contextlib.contextmanager
def span(name: str) -> Iterator[None]:
    """Scoped span: an ``nvtx.annotate`` range that also records call
    count and wall time, attributed to the enclosing range."""
    if not get_registry().enabled:
        yield
        return
    parent = nvtx.current_range() or ""
    t0 = time.perf_counter()
    error = False
    try:
        with nvtx.annotate(name):
            yield
    except BaseException:
        error = True
        raise
    finally:
        _record(name, parent, time.perf_counter() - t0, 0, 0, error)


def instrument(name: Optional[str] = None) -> Callable:
    """Decorator marking a hot-path primitive for observation.

    Records per call: ``raft_tpu_span_calls_total``, dispatch wall time
    into ``raft_tpu_span_seconds``, array bytes in/out, plus a span
    event — all labeled ``{span=<name>, range=<enclosing range>}``.
    ``tools/check_instrumented.py`` statically asserts the hot-path
    modules apply this decorator.
    """

    def decorate(fn: Callable) -> Callable:
        span_name = name or f"{fn.__module__}.{fn.__qualname__}"
        if ENV_DISABLED:
            # the documented near-zero-overhead contract: no wrapper at all
            fn.__instrumented__ = span_name
            return fn

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not get_registry().enabled:
                return fn(*args, **kwargs)
            parent = nvtx.current_range() or ""
            bytes_in = tree_nbytes((args, kwargs))
            t0 = time.perf_counter()
            error = False
            try:
                with nvtx.annotate(span_name):
                    out = fn(*args, **kwargs)
            except BaseException:
                error = True
                raise
            finally:
                if error:
                    _record(span_name, parent, time.perf_counter() - t0,
                            bytes_in, 0, True)
            _record(span_name, parent, time.perf_counter() - t0,
                    bytes_in, tree_nbytes(out), False)
            return out

        wrapper.__instrumented__ = span_name
        return wrapper

    return decorate
