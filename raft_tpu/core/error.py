"""Error system.

TPU-native equivalent of the reference's exception hierarchy and check macros
(ref: cpp/include/raft/core/error.hpp — ``raft::exception`` with backtrace,
``RAFT_EXPECTS`` / ``RAFT_FAIL``, and the per-vendor-library error macros).
On TPU there are no cublas/cusolver/cusparse/nccl handles; what remains is a
single device-error type for XLA-side failures plus the logic/runtime pair.
Python already attaches tracebacks to exceptions, so no manual backtrace
capture is needed.
"""

from __future__ import annotations

import contextlib
from typing import Iterator, List, Optional


class RaftException(Exception):
    """Base exception. (ref: core/error.hpp ``raft::exception``)"""


class LogicError(RaftException):
    """Invalid API usage / failed precondition.
    (ref: core/error.hpp ``raft::logic_error``)"""


def _flight_tail() -> List[dict]:
    """Last ~64 flight-recorder events at error-construction time —
    attached to device/deadline errors the way the span stack is, so a
    failure carries its own timeline. [] when tracing is disabled (no
    allocation); NEVER raises (an error constructor must not fail)."""
    try:
        from raft_tpu.observability.flight import error_tail

        return error_tail()
    except Exception:
        return []


class DeviceError(RaftException):
    """Accelerator-side failure (XLA compile/runtime error surfaced to the
    host). Carries ``flight_tail`` — the last ~64 timeline events at
    construction time (see :mod:`raft_tpu.observability.flight`).
    (ref: core/error.hpp ``raft::cuda_error``)"""

    def __init__(self, *args):
        super().__init__(*args)
        self.flight_tail = _flight_tail()


class OutOfMemoryError(DeviceError):
    """HBM exhaustion. (ref: rmm::bad_alloc path)"""


class DeadlineExceededError(RaftException):
    """A :func:`raft_tpu.resilience.deadline` scope expired before the
    guarded work completed — the TPU rendering of an NCCL collective
    timeout / watchdog abort. Carries the deadline budget, the active
    span stack of the cancelled thread at raise time, and the
    flight-recorder tail (``flight_tail``), so a hang converted into
    this error names WHERE the program was stuck and what led up to it.
    (ref: ncclCommAbort + the reference's interruptible::synchronize
    raising out of a spinning stream wait.)"""

    def __init__(self, message: str, seconds: Optional[float] = None,
                 span_stack: Optional[List[str]] = None):
        super().__init__(message)
        self.seconds = seconds
        self.span_stack = list(span_stack or [])
        self.flight_tail = _flight_tail()


# substrings of XLA / runtime status messages, checked upper-cased.
# RESOURCE_EXHAUSTED is the status code jaxlib surfaces for HBM/host
# allocation failure; the rest cover the prose variants seen in practice.
_OOM_MARKERS = ("RESOURCE_EXHAUSTED", "RESOURCE EXHAUSTED",
                "OUT OF MEMORY", "FAILED TO ALLOCATE", "ALLOCATION FAIL",
                "SCOPED-VMEM", "EXCEEDED MEMORY")
_DEADLINE_MARKERS = ("DEADLINE_EXCEEDED", "DEADLINE EXCEEDED",
                     "TIMED OUT", "TIMEOUT")
_DEVICE_MARKERS = ("INTERNAL:", "ABORTED:", "UNAVAILABLE:",
                   "DATA CORRUPTION", "HALT")


def _is_xla_error(exc: BaseException) -> bool:
    """jaxlib-layer exception, duck-typed by class name/module so the
    classifier needs no jaxlib import (and unit tests can use stubs)."""
    for klass in type(exc).__mro__:
        if klass.__name__ in ("XlaRuntimeError", "JaxRuntimeError"):
            return True
        if klass.__module__.split(".")[0] in ("jaxlib", "jax"):
            return True
    return False


def classify_xla_error(exc: BaseException) -> Optional[RaftException]:
    """Map a raw runtime exception onto the raft error classes, or None.

    (ref: core/error.hpp's per-status ``RAFT_CUDA_TRY`` expansion — each
    vendor status code became a typed raft exception. On TPU the vendor
    surface is jaxlib's ``XlaRuntimeError`` whose *message* carries the
    absl status code.) Mapping: RESOURCE_EXHAUSTED/OOM →
    :class:`OutOfMemoryError`; DEADLINE_EXCEEDED/timeout →
    :class:`DeadlineExceededError`; INTERNAL/ABORTED (or any other
    jaxlib-layer failure) → :class:`DeviceError`. Exceptions already in
    the error classes pass through unchanged; exceptions that are neither
    (``ValueError`` from user input, ``KeyboardInterrupt``…) return
    None — the caller re-raises them unwrapped.

    Every classification is also a flight-recorder trigger: an
    ``error`` timeline event is emitted and, when
    ``RAFT_TPU_FLIGHT_DIR`` is set, the ring is dumped as Perfetto
    JSON for post-mortem — once per exception instance, so an error
    bubbling through nested ``device_errors`` scopes dumps once."""
    if isinstance(exc, RaftException):
        _flight_on_classify(exc)
        return exc
    if not isinstance(exc, Exception):
        return None          # KeyboardInterrupt/SystemExit are not ours
    msg = str(exc)
    upper = msg.upper()
    is_xla = _is_xla_error(exc)
    label = f"[{type(exc).__name__}] {msg}"
    classified: Optional[RaftException] = None
    if any(m in upper for m in _OOM_MARKERS):
        classified = OutOfMemoryError(label)
    elif is_xla and any(m in upper for m in _DEADLINE_MARKERS):
        classified = DeadlineExceededError(label)
    elif is_xla or any(m in upper for m in _DEVICE_MARKERS):
        classified = DeviceError(label)
    if classified is not None:
        _flight_on_classify(classified)
    return classified


def _flight_on_classify(error: RaftException) -> None:
    """Timeline event + post-mortem dump for one classified device
    failure — once per exception instance; never raises."""
    if getattr(error, "_flight_dumped", False):
        return
    try:
        error._flight_dumped = True
        from raft_tpu.observability import flight
        from raft_tpu.observability.timeline import emit_error

        emit_error(type(error).__name__, str(error))
        flight.post_mortem(f"classify-{type(error).__name__}",
                           error=error)
    except Exception:
        pass


@contextlib.contextmanager
def device_errors(context: str = "") -> Iterator[None]:
    """Scope that re-raises device-layer failures classified into the
    raft error classes (chained via ``raise ... from``), so callers of the
    runtime entry points never see raw jaxlib exceptions. Non-device
    exceptions propagate unwrapped. (ref: the RAFT_CUDA_TRY macro
    bracket around every launch.)"""
    try:
        yield
    except RaftException:
        raise
    except Exception as e:
        classified = classify_xla_error(e)
        if classified is not None:
            if context:
                classified.args = (f"{context}: {classified.args[0]}",)
            raise classified from e
        raise


def expects(condition: bool, fmt: str, *args) -> None:
    """Check a precondition; raise :class:`LogicError` on failure.
    (ref: core/error.hpp ``RAFT_EXPECTS``)"""
    if not condition:
        raise LogicError(fmt % args if args else fmt)


def fail(fmt: str, *args) -> None:
    """Unconditionally raise :class:`LogicError`.
    (ref: core/error.hpp ``RAFT_FAIL``)"""
    raise LogicError(fmt % args if args else fmt)
